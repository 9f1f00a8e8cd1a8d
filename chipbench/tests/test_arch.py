"""The architecture modules behind ``chipbench/arch/``: the Qwen2 module
gives the leaves, counts and weights the benchmark had before it was
split out, and a second architecture, defined here and found by the
loader, drives the reference with no edit to any file of the benchmark.

    python3 -m pytest chipbench/tests -q      # by hand; not in tier-1
"""
import hashlib
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import arch, common, flops, reference, train  # noqa: E402

SMALL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
# sha256 over each leaf's path and bytes, in leaf order, of the weights
# that the benchmark made from PRNGKey(7) at SMALL before the Qwen2 code
# moved into chipbench/arch/qwen2.py
SMALL_DIGESTS = {
    "float32":
        "b41c922af7e06a0d4323ff35e07fc5c621b91b38f399bfa0b19123871e5820bf",
    "bfloat16":
        "719dbb047a2b3913287f3d5303a86125af0bdaee299752682ce6a018b4355e57",
}


def load(name: str, kind: str = "configs") -> dict:
    with open(os.path.join(HERE, "..", kind, f"{name}.json")) as f:
        return json.load(f)


def test_qwen2_leaves_are_the_stored_layout():
    config = load("qwen2.5-3b-train-3L")
    g = "groups/l0_attn/"
    want = [
        ("embed/table", (151936, 2048)),
        (g + "attn/bk", (3, 2, 128)), (g + "attn/bq", (3, 16, 128)),
        (g + "attn/bv", (3, 2, 128)),
        (g + "attn/wk", (3, 2048, 2, 128)),
        (g + "attn/wo", (3, 16, 128, 2048)),
        (g + "attn/wq", (3, 2048, 16, 128)),
        (g + "attn/wv", (3, 2048, 2, 128)),
        (g + "mlp/wg", (3, 2048, 11008)), (g + "mlp/wi", (3, 2048, 11008)),
        (g + "mlp/wo", (3, 11008, 2048)),
        (g + "norm1/scale", (3, 2048)), (g + "norm2/scale", (3, 2048)),
        ("final_norm/scale", (2048,)),
    ]
    got = arch.load(config).leaves(config)
    assert [(reference.leaf_name(p), s) for p, s, _ in got] == want
    # one trust ratio per stored tensor of two or more dimensions, the
    # stacked biases and norm scales among them; the final norm plain
    assert [r for _, _, r in got] == ["adapt"] * 13 + ["plain"]


def test_qwen2_counts():
    config = load("qwen2.5-3b-train-3L")
    m = arch.load(config)
    assert m.param_count(config) == 542_397_952
    assert flops.update_bytes(config) == 20 * 542_397_952
    # test_flops.py's value
    assert flops.train_flops_per_token(config, 2048) == \
        6 * 542_375_936 + 12 * 3 * 2048 * 2048


@pytest.mark.parametrize("dtype", sorted(SMALL_DIGESTS))
def test_qwen2_weights_are_the_benchmarks_own(dtype):
    import jax
    config = dict(load("qwen2.5-3b-train-3L"), **SMALL)
    make = arch.load(config).init_weights(config, dtype)
    w = jax.jit(make)(jax.random.PRNGKey(7))
    h = hashlib.sha256()
    for (path, _, _), leaf in zip(arch.load(config).leaves(config),
                                  reference.flat(w, config)):
        h.update(reference.leaf_name(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == SMALL_DIGESTS[dtype]


def test_an_unknown_model_type_names_the_file_to_add():
    with pytest.raises(common.BenchError, match=r"chipbench/arch/nosuch\.py"):
        arch.load({"name": "x", "model_type": "nosuch"})
    with pytest.raises(common.BenchError, match="model_type"):
        arch.load({"name": "x"})


# --------------------------------------------------------------------------
# a second architecture, defined here: two residual tanh layers over a
# tied table, and a logit bias that the optimizer never touches and
# after_step moves against the step's gold-token counts (as a router's
# selection bias follows its experts' load)
# --------------------------------------------------------------------------

TOY = "toy_held_bias"
BIAS_STEP = 1e-3


def toy_module(seen: list) -> types.ModuleType:
    import jax
    import jax.numpy as jnp

    mod = types.ModuleType(f"chipbench.arch.{TOY}")

    def leaves(config):
        v, d = config["vocab_size"], config["hidden_size"]
        return [(("embed", "table"), (v, d), "adapt"),
                (("layers", "w"), (2, d, d), "adapt"),
                (("norm", "scale"), (d,), "plain"),
                (("logit_bias",), (v,), "held")]

    def init_weights(config, dtype):
        def make(key):
            return reference.nest(
                (path, (0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                                shape)).astype(dtype))
                for i, (path, shape, _) in enumerate(leaves(config)))
        return make

    def row_loss(config, mm, params, row):
        table = params["embed"]["table"]
        x = table[row[:-1]]
        for i in range(2):
            x = x + jnp.tanh(mm("sd,de->se", x, params["layers"]["w"][i]))
        x = x * (1.0 + params["norm"]["scale"])
        lg = mm("sd,vd->sv", x, table) + params["logit_bias"]
        gold = row[1:]
        ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, gold[:, None], -1)[:, 0]
        hits = jax.nn.one_hot(gold, config["vocab_size"]).sum(0)
        return jnp.mean(ce), {"hits": hits}

    def after_step(config, params, aux):
        b = params["logit_bias"]
        hits = aux["hits"]
        new = b + BIAS_STEP * jnp.sign(jnp.mean(hits) - hits)
        jax.debug.callback(lambda x, y: seen.append(
            (np.asarray(x), np.asarray(y))), b, new)
        return dict(params, logit_bias=new)

    mod.leaves = leaves
    mod.init_weights = init_weights
    mod.row_loss = row_loss
    mod.after_step = after_step
    mod.matmul_params_per_token = lambda c: 3 * c["vocab_size"] \
        * c["hidden_size"]
    mod.attention_flops_per_token = lambda c, context: 0.0
    mod.param_count = lambda c: sum(int(np.prod(s)) for _, s, _ in
                                    leaves(c))
    return mod


def toy_config() -> dict:
    return {"name": "toy", "model_type": TOY, "vocab_size": 48,
            "hidden_size": 16,
            "optimizer": load("qwen2.5-3b-train-3L")["optimizer"]}


def test_a_second_architecture_joins_by_a_new_module(monkeypatch):
    import jax
    import jax.numpy as jnp
    seen = []
    monkeypatch.setitem(sys.modules, f"chipbench.arch.{TOY}",
                        toy_module(seen))
    config = toy_config()
    traffic = dict(load("accum8", "workloads"), accum_steps=4, seq_len=32)
    hp = train.hyper(config, traffic)
    key = reference.base_key(2**31 + 404)
    rows_of = train.reference_rows(config, key, 4, 32)
    ref = reference.train_readings(config, hp, key, rows_of, 32, 3)

    assert all(np.isfinite(ref["losses"]))
    bias0 = np.asarray(jax.jit(reference.weights_fn(config, jnp.float32))(
        key)["logit_bias"])
    # the held leaf has a gradient, and the optimizer leaves it alone:
    # after_step sees it as it left it the step before, bit for bit
    assert ref["grad_norms"][3] > 0
    assert len(seen) == 3
    before = bias0
    for i, (got, new) in enumerate(seen):
        np.testing.assert_array_equal(got, before)
        hits = np.asarray(jax.nn.one_hot(rows_of(i)[:, 1:], 48)).sum((0, 1))
        np.testing.assert_allclose(
            new - got, BIAS_STEP * np.sign(hits.mean() - hits), atol=1e-7)
        before = new
    np.testing.assert_allclose(ref["delta_norms"][3],
                               np.linalg.norm(before - bias0), rtol=1e-6)
    # the leaves the optimizer updates have moved
    assert min(ref["delta_norms"][:3]) > 0

    # the check reads the reference against itself as a sound run: the
    # held leaf is in the change and not in the read-back gradient
    check = train.numbers(traffic, config, hp, ref["losses"], ref["first"],
                          ref["delta_norms"], ref)
    assert check.items["loss_gap"]["value"] == 0.0
    assert check.items["update_norm_gap"]["value"] == 0.0
    assert check.correct, check.report()

    # the counts come from the module
    assert flops.update_bytes(config) == 20 * (48 * 16 + 2 * 16 * 16 + 16
                                               + 48)
    assert flops.train_flops_per_token(config, 32) == 6.0 * 3 * 48 * 16
