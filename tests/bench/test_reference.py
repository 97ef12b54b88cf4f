"""The plain reference against the served model at a small size: the
dense prefill's logits and the paged decode's logits, on weights that
the benchmark makes from a seed (one past 32 bits).  Weights and
reference come from the dense family, found by name as a run finds
it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness

SEED = (1 << 33) + 12345
DENSE = harness.family(harness.ROOT / "bench" / "families" / "dense.py")

MODELS = {
    "qwen2-like": dict(
        arch="qwen2-1.5b", num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        mlp_activation="swiglu", qkv_bias=True, tie_embeddings=True,
        rope_theta=1e6, norm_eps=1e-5, dtype="bfloat16"),
    "granite-like": dict(
        arch="granite-8b", num_layers=2, d_model=64, num_heads=8,
        num_kv_heads=4, head_dim=8, d_ff=96, vocab_size=200,
        mlp_activation="swiglu", qkv_bias=False, tie_embeddings=False,
        rope_theta=1e7, norm_eps=1e-5, dtype="bfloat16"),
}
# bf16 weights and activations against a float32 forward: the
# program's logits stay within a few hundredths of the logit range
REL_TOL = 0.05


def program(m):
    from repro.configs import get_config
    from repro.models.model import Model

    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads",
            "head_dim", "d_ff", "vocab_size", "qkv_bias", "tie_embeddings",
            "rope_theta")
    cfg = dataclasses.replace(get_config(m["arch"]),
                              **{k: m[k] for k in keys})
    return Model(cfg, remat=False)


def test_layer_by_layer_weights_match_the_stacked_ones():
    m = MODELS["qwen2-like"]
    p = DENSE.make(m, SEED)
    for i in range(m["num_layers"]):
        one = DENSE.layer(m, SEED, i)
        same = jax.tree_util.tree_map(
            lambda a, b: bool((a[i] == b).all()), p["layers"], one)
        assert all(jax.tree_util.tree_leaves(same))
    assert bool((DENSE.outer(m, SEED)["embed"] == p["embed"]).all())
    other = DENSE.make(m, SEED + 1)
    assert not bool((other["embed"] == p["embed"]).all())


@pytest.mark.parametrize("name", sorted(MODELS))
def test_reference_matches_the_dense_prefill(name):
    m = MODELS[name]
    model = program(m)
    params = DENSE.make(m, SEED)
    toks = np.random.default_rng(0).integers(1, m["vocab_size"], 37)
    got, _ = model.prefill(params, jnp.asarray(toks)[None])
    ref = np.asarray(DENSE.logits(m, SEED, toks, [36]))[0]
    got = np.asarray(got[0, -1], np.float32)
    assert np.abs(got - ref).max() <= REL_TOL * np.abs(ref).max()


@pytest.mark.parametrize("name", sorted(MODELS))
def test_reference_matches_paged_decode(name):
    from repro.runtime.serve_loop import ServeEngine

    m = MODELS[name]
    model = program(m)
    params = DENSE.make(m, SEED)
    eng = ServeEngine(model, params, num_pages=64, page_size=8,
                      max_pages_per_seq=16, attn_impl="fused_ref")
    prompt = np.random.default_rng(1).integers(1, m["vocab_size"], 21)
    sid = eng.add_request(prompt.tolist())
    rows = []
    for _ in range(6):
        eng.decode([sid], greedy=True)
        rows.append(np.asarray(eng.last_logits[0], np.float32))
    toks = eng.tokens(sid)
    pos = np.arange(len(prompt) - 1, len(toks) - 1)
    ref = np.asarray(DENSE.logits(m, SEED, toks, pos))
    got = np.stack(rows)
    assert np.abs(got - ref).max() <= REL_TOL * np.abs(ref).max()
    # and the check's own numbers read the same served positions
    read = check.compare(DENSE, m, SEED,
                         [(prompt.tolist(), toks[len(prompt):], got)])
    assert read["logit_rel_err"] <= REL_TOL
    assert read["greedy_argmax_mismatches"] == 0


def test_float8_control_departs_further_than_the_program():
    m = MODELS["qwen2-like"]
    model = program(m)
    params = DENSE.make(m, SEED)
    toks = np.random.default_rng(2).integers(1, m["vocab_size"], 64)
    pos = np.arange(32, 63)
    ref = np.asarray(DENSE.logits(m, SEED, toks, pos))
    low = np.asarray(DENSE.logits(m, SEED, toks, pos, "fp8"))
    err_low = np.abs(low - ref).max()
    # the program in bf16 at the last position
    last, _ = model.prefill(params, jnp.asarray(toks[:63])[None])
    err_prog = np.abs(np.asarray(last[0, -1], np.float32) - ref[-1]).max()
    assert err_low > 3 * err_prog
