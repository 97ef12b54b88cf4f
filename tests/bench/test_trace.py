"""The trace reduction and the work arithmetic, against hand-computed
values and against a small trace recorded on one TPU v5e (three fused
decode steps of qwen2-1.5b at 8 rows, 1,040 pages per row)."""

import gzip
import json
from pathlib import Path

import pytest

from bench import harness, trace as T, work

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_decode_trace.json.gz"


def plane(ops, modules=()):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [list(e) + [{}] for e in ops]},
        {"name": "XLA Modules", "events": [list(e) + [{}] for e in modules]},
    ]}


def test_union_busy_and_gaps():
    p = plane([("%a.1 = f32[] x()", 0, 10), ("%b.2 = f32[] y()", 5, 10),
               ("%c.3 = f32[] z()", 30, 5)])
    assert T.union([(0, 10), (5, 15), (30, 35)]) == [(0, 15), (30, 35)]
    assert T.busy_ns(p, 0, 40) == 20
    assert T.busy_ns(p, 10, 32) == 7
    assert T.idle_gaps(p, 0, 40) == [(15, 30), (35, 40)]


def test_self_time_of_nested_ops():
    # a while loop spanning two ops of its body, and one op after it
    p = plane([("%while.3 = (s32[]) while()", 0, 100),
               ("%paged_attention.6 = bf16[8] custom-call()", 10, 30),
               ("%fusion.1 = bf16[8] fusion()", 50, 20),
               ("%copy.4 = bf16[8] copy()", 120, 5)])
    by = T.op_time_by_name(p, 0, 200)
    assert by == {"while": 50.0, "paged_attention": 30.0, "fusion": 20.0,
                  "copy": 5.0}
    assert T.kernel_ns(p, "paged_attention", 0, 200) == 30.0
    assert T.kernel_calls(p, "paged_attention", 0, 200) == 1
    assert T.op_name("%paged_attention.17 = bf16[1] custom-call()") == \
        "paged_attention"


def test_ops_inside_and_outside_a_program():
    p = plane([("%f.1 = f32[] a()", 0, 10), ("%g.2 = f32[] b()", 20, 10),
               ("%h.3 = f32[] c()", 40, 4)],
              modules=[("jit_step(1)", 0, 32), ("jit_scatter(2)", 40, 4)])
    assert T.ops_inside(p, "jit_step", 0, 100) == 20.0
    assert T.ops_inside(p, "jit_step", 0, 100, inside=False) == 4.0


def test_idle_gaps_go_to_the_innermost_host_span():
    spans = [("drv.step", 0, 100), ("engine.decode", 10, 60),
             ("session.admit", 70, 90)]
    gaps = [(20, 30), (75, 85), (95, 99), (150, 160)]
    got = T.attribute_gaps(gaps, spans)
    assert got == {"engine.decode": 10, "session.admit": 10,
                   "drv.step": 4, "no span": 10}
    assert T.top(got, 2, 1.0) == [["engine.decode", 10], ["session.admit",
                                                         10]]


def test_percentile_is_an_exact_sample():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0, 10.0, 9.0, 8.0, 7.0, 6.0]
    assert T.percentile(xs, 50) == 5.0
    assert T.percentile(xs, 90) == 9.0
    assert T.percentile(xs, 95) == 10.0
    assert T.percentile(xs, 100) == 10.0
    assert T.percentile([3.0], 90) == 3.0


def test_recorded_v5e_trace():
    tr = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    planes = T.device_planes(tr)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    p = planes[0]
    steps = T.host_spans(tr, ["drv.step"])
    assert len(steps) == 3
    lo = min(s for _, s, _ in steps)
    hi = max(e for _, _, e in steps)
    assert (lo, hi) == (45799400.0, 1914007582.0)
    # one kernel call per layer per step: 3 steps x 28 layers
    assert T.kernel_calls(p, "paged_attention", lo, hi) == 84
    assert T.kernel_ns(p, "paged_attention", lo, hi) == 196681791.0
    # the three decode programs ran 320,451,189 ns; a few small eager
    # ops (argmax, the token gather) make up the rest of the busy time
    busy = T.busy_ns(p, lo, hi)
    assert busy == 320527137.0
    inside = T.ops_inside(p, "jit_paged_fused_decode_step", lo, hi)
    outside = T.ops_inside(p, "jit_paged_fused_decode_step", lo, hi,
                           inside=False)
    assert inside <= 320451189.0
    assert 0 < outside < 1e6
    by = T.op_time_by_name(p, lo, hi)
    assert max(by, key=by.get) == "paged_attention"
    assert sum(by.values()) == pytest.approx(inside + outside)
    gaps = T.idle_gaps(p, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)


def test_work_arithmetic():
    m = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 10,
         "mlp_activation": "swiglu", "dtype": "bfloat16"}
    dense = harness.family(harness.ROOT / "bench" / "families" / "dense.py")
    # q 8*4*2 + k,v 2*8*2*2 + o 4*2*8 ; mlp 3*8*16
    assert dense.layer_matmul_params(m) == 64 + 64 + 64 + 384
    # 4 heads * 2 dims * 4 * (1*10 + 1)
    assert work.attention_flops(m, 10, 1) == 4 * 4 * 2 * 11
    assert dense.token_flops(m, 10) == 2 * (2 * 576 + 352) + 2 * 8 * 10
    fl, by = work.paged_attention_work(m, [(10, 1), (0, 3)], "bfloat16")
    assert dense.attention_work(m, [(10, 1), (0, 3)], "bfloat16") \
        == [(fl, by)] * 2
    assert fl == 4 * 4 * 2 * (11 + 6)
    # cached K+V, the chunk's K+V, queries and output, 2 bytes each
    assert by == 2 * (10 * 2 * 2 * 2) + (2 * 1 * 2 * 2 * 2 + 2 * 1 * 4 * 2 * 2) \
        + (2 * 3 * 2 * 2 * 2 + 2 * 3 * 4 * 2 * 2)
    pk = work.peaks("TPU v5 lite")
    t, bound = work.roofline_seconds(197e12, 819e9 * 2, pk)
    assert (t, bound) == (2.0, "memory")
    with pytest.raises(KeyError):
        work.peaks("TPU v4")
