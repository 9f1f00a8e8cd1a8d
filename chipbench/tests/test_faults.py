"""A run with the timed path broken underneath must come out not
correct, and a sound run correct: the runners of the cells at a tiny
size on the CPU (the look for a chip is skipped), compared by the
cells' own limits.

    python3 -m pytest chipbench/tests -q      # by hand; not in tier-1

Faults planted, one per test: a train step that returns its state
unchanged; half of the batch left out, the mean taken over the rest;
the gradient exchange between four devices left out (each device keeps
its own gradients); a served token altered where it is produced.
"""
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)


def load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "chipbench", kind, f"{name}.json")) as f:
        return json.load(f)


def train_cell(traffic: str, accum: int = 4):
    config = load("configs", "qwen2.5-3b-train-3L")
    config.update(TINY)
    # at these widths bfloat16 rounding alone reads above the cell's
    # limits, which were set from readings at the published widths
    config["program"]["compute_dtype"] = "float32"
    tr = load("workloads", traffic)
    tr.update(accum_steps=accum, seq_len=64)
    return config, tr


def run_train(config, tr, step_factory=None, seed=2**31 + 77):
    import jax
    from chipbench import train
    devices = jax.devices()[:tr["data_parallel"]]
    _, check, _ = train.run(config, tr, seed, 0.5, False, devices,
                            time.perf_counter(), step_factory=step_factory)
    return check


def test_train_sound_run_is_correct():
    check = run_train(*train_cell("accum8"))
    assert check.correct, check.report()


def test_train_state_unchanged_is_not_correct():
    from repro.training import trainer

    def factory(model, opt, accum_steps, mesh):
        real = trainer.make_train_step(model, opt, accum_steps=accum_steps,
                                       mesh=mesh)

        def step(state, batch):
            _, metrics = real(state, batch)
            return state._replace(step=state.step + 1), metrics
        return step

    check = run_train(*train_cell("accum8"), step_factory=factory)
    assert not check.correct
    assert check.items["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch_is_not_correct():
    from repro.training import trainer

    def factory(model, opt, accum_steps, mesh):
        half = trainer.make_train_step(model, opt,
                                       accum_steps=accum_steps // 2,
                                       mesh=mesh)

        def step(state, batch):
            return half(state, {k: v[:accum_steps // 2]
                                for k, v in batch.items()})
        return step

    check = run_train(*train_cell("accum8"), step_factory=factory)
    assert not check.correct, check.report()


DP_SCRIPT = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
import jax
from test_faults import train_cell, run_train
from repro.training import trainer

def factory(model, opt, accum_steps, mesh):
    if {broken}:
        jax.lax.pmean = lambda x, axes: x
    return trainer.make_train_step(model, opt, accum_steps=accum_steps,
                                   mesh=mesh)

check = run_train(*train_cell("dp4-accum4", accum=2), step_factory=factory)
print(json.dumps({{"correct": check.correct, "checks": check.report()}}))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_train_no_exchange_between_devices(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DP_SCRIPT.format(root=ROOT, src=os.path.join(ROOT, "src"),
                            here=HERE, broken=broken)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not broken), res


def serve_cell():
    config = load("configs", "qwen2.5-3b-serve")
    config.update(TINY)
    config["serve"].update(slots=4, max_len=128, prefill_batch=2)
    tr = load("workloads", "chat-poisson")
    tr.update(rate_per_s=4.0, warm_prefill_lengths=[16, 32, 64],
              warm_prefill_batches=[1, 2], sample_requests=4)
    tr["prompt_len"].update(median=24, min=16, max=64)
    tr["output_len"].update(median=12, min=4, max=32)
    return config, tr


def run_serve(hook=None):
    import jax
    from chipbench import serve
    config, tr = serve_cell()
    _, check, _ = serve.run(config, tr, 2**31 + 99, 4.0, False,
                            jax.devices()[:1], time.perf_counter(),
                            engine_hook=hook)
    return check


def test_serve_sound_run_is_correct():
    check = run_serve()
    assert check.correct, check.report()


def test_serve_altered_token_is_not_correct():
    import jax

    def hook(eng):
        decode = eng._decode
        vocab = eng.model.cfg.vocab_size

        @jax.jit
        def altered(*args):
            nxt, cache = decode(*args)
            return (nxt + 1) % vocab, cache
        eng._decode = altered

    check = run_serve(hook)
    assert not check.correct
    assert check.items["logit_gap"]["value"] > 0
