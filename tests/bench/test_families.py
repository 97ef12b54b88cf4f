"""The dense family, found by name, gives what the benchmark gave before
its code moved into ``bench/families/dense.py``.

The fixture ``fixtures/dense_parent.npz`` was written by that earlier
code (``weights.make`` and ``reference.logits`` at a reduced qwen2 size,
on the CPU): a SHA-256 digest of each weight's bytes, and the reference
logits in float32 and with the float8 control.  The work counts are
checked against their formulas written out here, at the widths of
``bench/configs/qwen2-1.5b.json``.
"""

import hashlib
import json

import jax
import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT
FIXTURE = ROOT / "tests" / "bench" / "fixtures" / "dense_parent.npz"
SEED = (1 << 33) + 12345
QWEN2_LIKE = dict(
    arch="qwen2-1.5b", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
    mlp_activation="swiglu", qkv_bias=True, tie_embeddings=True,
    rope_theta=1e6, norm_eps=1e-5, dtype="bfloat16")


@pytest.fixture(scope="module")
def dense():
    conf = json.loads((ROOT / "bench" / "configs" /
                       "qwen2-1.5b.json").read_text())
    assert conf["family"] == "dense"
    return harness.family(ROOT / "bench" / "families" / "dense.py")


@pytest.fixture(scope="module")
def parent():
    return dict(np.load(FIXTURE))


def _digest(a: np.ndarray) -> str:
    return hashlib.sha256(a.tobytes() + str(a.dtype).encode()
                          + str(a.shape).encode()).hexdigest()


def test_dense_weights_are_bitwise_the_earlier_ones(dense, parent):
    leaves = jax.tree_util.tree_flatten_with_path(
        dense.make(QWEN2_LIKE, SEED))[0]
    got = {jax.tree_util.keystr(p): _digest(np.asarray(a))
           for p, a in leaves}
    assert got == dict(zip(parent["leaves"].tolist(),
                           parent["digests"].tolist()))


@pytest.mark.parametrize("precision", ["fp32", "fp8"])
def test_dense_reference_logits_are_the_earlier_ones(dense, parent,
                                                     precision):
    got = np.asarray(dense.logits(QWEN2_LIKE, SEED, parent["tokens"],
                                  parent["positions"], precision))
    np.testing.assert_array_equal(got, parent[precision])


def test_dense_work_counts_follow_their_formulas(dense):
    m = json.loads((ROOT / "bench" / "configs" /
                    "qwen2-1.5b.json").read_text())["model"]
    L, d, h, kv, hd, f, V = (28, 1536, 12, 2, 128, 8960, 151936)
    assert (m["num_layers"], m["d_model"], m["num_heads"],
            m["num_kv_heads"], m["head_dim"], m["d_ff"],
            m["vocab_size"]) == (L, d, h, kv, hd, f, V)
    weights = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    for n in (0, 1, 8192, 11000, 16384):
        want = L * (2.0 * weights + 4.0 * h * hd * (n + 1)) + 2.0 * d * V
        assert dense.token_flops(m, n) == want
    rows = [(8192, 1), (10999, 1), (8000, 360)]
    flops = sum(4.0 * h * hd * (t * n + t * (t + 1) / 2) for n, t in rows)
    nbytes = sum(2.0 * n * kv * hd * 2 + 2.0 * t * kv * hd * 2
                 + 2.0 * t * h * hd * 2 for n, t in rows)
    assert dense.attention_work(m, rows, "bfloat16") == [(flops, nbytes)] * L
