"""The controls of the cells at a small size on the CPU. Training: the
reference computed in float8 in the program's place must come out not
correct by the cell's own limits, and the float32 reference in its
place correct. Serving: ``control.py``'s serving path at 512 wide, where the
float8 control's widest gap must exceed the bfloat16 program's and fail
the comparison that the program passes. At this size both lie far
under the cell's limit, which was set from readings at the published
widths, so the test compares with one set from readings at this size
on these seeds: the program 0.0, the control 0.075 and 0.082.

    python3 -m pytest chipbench/tests -q      # by hand; not in tier-1

On the chip, ``chipbench/control.py`` reads the same at the cells' own
sizes; PERF.md gives those readings.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_faults import one_row_cell, serve_cell, train_cell  # noqa: E402


def readings(config, tr, precision, keep=None, seed=2**31 + 5):
    from chipbench import reference, train
    hp = train.hyper(config, tr)
    rows = tr["accum_steps"] * tr["microbatch"] * tr["data_parallel"]
    S, steps = tr["seq_len"], tr["checked_steps"]
    key = reference.base_key(seed)
    rows_of = train.reference_rows(config, key, rows, S)
    ref = reference.train_readings(config, hp, key, rows_of, S, steps, "f32")
    got = reference.train_readings(config, hp, key, rows_of, S, steps,
                                   precision, keep)
    return train.numbers(tr, config, hp, got["losses"], got["first"],
                         got["delta_norms"], ref)


@pytest.mark.parametrize("cell", ["accum8", "accum1"])
def test_train_float8_control_is_not_correct(cell):
    config, tr = one_row_cell() if cell == "accum1" else train_cell(cell)
    assert readings(config, tr, "f32").correct
    check = readings(config, tr, "fp8")
    assert not check.correct, check.report()


@pytest.mark.parametrize("seed", [2**31 + 99, 2**32 + 7])
def test_serve_float8_control_reads_wider_than_the_program(seed, capsys):
    import jax
    from chipbench import control
    config, tr = serve_cell()
    config.update(hidden_size=512, intermediate_size=1024,
                  num_hidden_layers=4, vocab_size=4096)
    tr.update(rate_per_s=4.0, sample_requests=8)
    tr["limits"]["logit_gap"] = 0.04
    control.serve_control(config, tr, [seed], 4.0, jax.devices()[:1])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"], line
    assert line["control_correct"] is False, line
    assert line["control_readings"]["logit_gap"]["value"] \
        > line["readings"]["logit_gap"]["value"], line
