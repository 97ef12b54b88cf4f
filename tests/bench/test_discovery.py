"""The harness finds every configuration, model family, traffic mix, cell
and metric by name from files; traffic is a pure function of its
parameters and the seed; a run without a TPU is refused."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import check, generator, harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    spec = harness.load_spec(cell)
    assert spec["config"]["name"] == spec["workload"]["config"]
    conf = next(c for c in SPEC["configs"]
                if c["name"] == spec["workload"]["config"])
    assert (ROOT / conf["file"]).is_file()
    assert sorted(conf["reduced"]) == sorted(spec["config"]["reduced"])
    for k in conf["reduced"]:
        assert k in spec["config"]["published"]
    assert set(spec["cell"]["limits"]) >= {"logit_rel_err",
                                          "greedy_argmax_mismatches"}
    harness.arch_config(spec["config"]["model"])
    fam = spec["family"]
    assert Path(fam.__file__) == (ROOT / "bench" / "families" /
                                  f"{spec['config']['family']}.py")
    for fn in ("make", "layer", "outer", "logits", "token_flops",
               "attention_work"):
        assert callable(getattr(fam, fn))
    for m in SPEC["end_to_end"]:
        assert m["name"] in harness.END_TO_END
    for m in SPEC["per_layer"]:
        assert callable(harness.metric_reader(spec["metrics_dir"],
                                              m["name"]))


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "bench").mkdir()
    for d in ("configs", "families", "traffic", "cells"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    (tmp_path / "bench" / "metrics").mkdir()
    spec = json.loads(json.dumps(SPEC))
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{spec['workloads'][0]['traffic']}.json")
                         .read_text())
    traffic["sessions"] = 3
    (tmp_path / "bench" / "traffic" / "agent-three.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "cells" / "qwen2-agent-three.json").write_text(
        (ROOT / "bench" / "cells" / "qwen2-agent-fanout.json").read_text())
    (tmp_path / "bench" / "metrics" / "admits_seen.py").write_text(
        "def read(run):\n    return run['requests_admitted'] or None\n")
    spec["workloads"].append({"name": "qwen2-agent-three",
                              "config": "qwen2-1.5b",
                              "traffic": "agent-three", "chips": 1,
                              "why": "a throwaway cell"})
    spec["per_layer"].append({"name": "admits_seen", "unit": "count",
                              "better": "higher",
                              "source": "program_counter", "layer": "x",
                              "moves": "ttft_p50_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    got = harness.load_spec("qwen2-agent-three", root=tmp_path)
    assert got["traffic"]["sessions"] == 3
    read = harness.metric_reader(got["metrics_dir"], "admits_seen")
    assert read({"requests_admitted": 7}) == 7
    assert read({"requests_admitted": 0}) is None
    with pytest.raises(harness.Refused):
        harness.load_spec("no-such-cell", root=tmp_path)


TOY_FAMILY = """
import numpy as np

CALLS = []


def make(m, seed):
    return {"seed": seed}


def layer(m, seed, i):
    return {"i": i}


def outer(m, seed):
    return {}


def logits(m, seed, tokens, positions, precision="fp32"):
    CALLS.append(precision)
    pos = np.asarray(positions)
    out = np.zeros((len(pos), m["vocab_size"]), np.float32)
    out[np.arange(len(pos)), np.asarray(tokens)[pos + 1]] = 1.0
    return out if precision == "fp32" else np.roll(out, 1, -1)


def token_flops(m, n_ctx):
    return 1000.0 + n_ctx


def attention_work(m, rows, kv_dtype):
    rows = list(rows)
    return [(float(sum(n for n, _ in rows)), float(len(rows)))] * 3
"""


def toy_root(tmp_path, conf_extra):
    """A checkout under ``tmp_path`` with one cell whose configuration
    is ``toy-model`` (``conf_extra`` merged into it)."""
    for d in ("traffic", "cells"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    (tmp_path / "bench" / "configs").mkdir()
    (tmp_path / "bench" / "families").mkdir()
    (tmp_path / "bench" / "families" / "toy.py").write_text(TOY_FAMILY)
    conf = json.loads((ROOT / "bench" / "configs" /
                       "qwen2-1.5b.json").read_text())
    conf.pop("family")
    conf.update(name="toy-model", **conf_extra)
    conf["model"] = dict(conf["model"], vocab_size=16)
    (tmp_path / "bench" / "configs" / "toy-model.json").write_text(
        json.dumps(conf))
    (tmp_path / "bench" / "cells" / "toy-cell.json").write_text(
        (ROOT / "bench" / "cells" / "qwen2-agent-fanout.json").read_text())
    spec = json.loads(json.dumps(SPEC))
    w = dict(spec["workloads"][0], name="toy-cell", config="toy-model")
    spec["workloads"].append(w)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_a_new_family_is_found_by_name(tmp_path):
    before = sorted(p.name for p in (ROOT / "bench" / "families").iterdir())
    spec = harness.load_spec("toy-cell",
                             root=toy_root(tmp_path, {"family": "toy"}))
    fam = spec["family"]
    assert Path(fam.__file__) == tmp_path / "bench" / "families" / "toy.py"
    model = spec["config"]["model"]
    # the check compares with the family's reference, and its control
    seqs = [([1, 2, 3], [4, 5], np.eye(16, dtype=np.float32)[[4, 5]])]
    assert check.compare(fam, model, 7, seqs) == {
        "logit_rel_err": 0.0, "greedy_argmax_mismatches": 0}
    ctl = check.compare(fam, model, 7, seqs, "fp8")
    assert ctl["greedy_argmax_mismatches"] == 2
    assert fam.CALLS == ["fp32", "fp32", "fp8"]
    # the work counted in a traced stretch is the family's
    rec = harness.Recorder(fam, model, "bfloat16")
    rec.count([(10, 1), (20, 1)], decode=True)
    rec.count([(30, 4)], decode=False)
    assert rec.attn_work == [(30.0, 2.0)] * 3 + [(30.0, 1.0)] * 3
    assert rec.decode_flops == 2030.0
    assert sorted(p.name for p in
                  (ROOT / "bench" / "families").iterdir()) == before


@pytest.mark.parametrize("extra", [{}, {"family": "no-such-family"}],
                         ids=["no-family-key", "no-family-file"])
def test_a_config_without_a_family_file_is_refused(tmp_path, extra):
    root = toy_root(tmp_path, extra)
    with pytest.raises(harness.Refused, match="family"):
        harness.load_spec("toy-cell", root=root)


TRAFFIC = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_is_a_pure_function_of_the_seed(name):
    t = json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())
    seed = (1 << 31) + 977          # larger than 32 signed bits hold
    a = generator.Schedule(t, seed, 1000)
    b = generator.Schedule(t, seed, 1000)
    c = generator.Schedule(t, seed + 1, 1000)
    for i in (0, 5, 3 * t["sessions"] + 1):
        assert a.session(i) == b.session(i)
        assert a.tool(i, 1, 9) == b.tool(i, 1, 9)
    assert a.system() == b.system()
    # another seed: the same scripts, order and gaps; other tokens
    assert a.scripts == c.scripts
    assert a.order == c.order and a.gaps == c.gaps
    assert sorted(a.order) == list(range(t["sessions"]))
    assert a.session(0)["task"] != c.session(0)["task"]
    assert a.tool(0, 1, 9) != c.tool(0, 1, 9)
    assert a.system() != c.system()
    # the gaps average to the mix's rate
    assert np.mean(a.gaps) == pytest.approx(1 / t["session_rate_per_s"])


@pytest.mark.parametrize("name", TRAFFIC)
def test_every_suffix_fits_what_the_kernel_compiles(name):
    """A turn prefills its uncached suffix through the paged-attention
    kernel, which compiles at most 360 query tokens at qwen2-1.5b
    widths (scoped VMEM); every suffix the mix can send stays inside."""
    t = json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())
    lengths = generator.suffix_lengths(t, 16)
    assert 0 < min(lengths) and max(lengths) <= 360
    turns = sum(len(s["turns"]) for s in generator.scripts(t))
    assert len(lengths) <= turns


def test_warm_up_prefills_every_suffix_the_mix_sends():
    """Set-up replays each script's prompts; the lengths it prefills are
    the ones the generator names."""
    from test_control import small_spec
    from repro.runtime.serve_loop import ServeEngine

    spec = small_spec(CELLS[0])
    seen = []
    orig = ServeEngine._chunk_prefill

    def chunk(self, sid, tokens, covered):
        seen.append(len(tokens) - covered)
        return orig(self, sid, tokens, covered)

    ServeEngine._chunk_prefill = chunk
    try:
        engine, session, driver = harness.build(
            spec, 5, impl="fused_ref", log=lambda *_: None)
        sched = generator.Schedule(spec["traffic"], 5,
                                   spec["config"]["model"]["vocab_size"])
        from bench.clients import AgentClient
        client = AgentClient(spec["traffic"], sched, driver,
                             harness.annotator(False))
        harness.warm_up(engine, client, spec["traffic"], 16, 5)
    finally:
        ServeEngine._chunk_prefill = orig
    want = generator.suffix_lengths(spec["traffic"], engine.page_size)
    assert set(want) <= set(seen)


def test_run_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_harness_refuses_attention_other_than_pallas():
    spec = harness.load_spec(CELLS[0])
    run = harness.Run(spec, 1, 1.0, False, t_start=0.0, impl="fused_ref",
                      require_pallas=True)
    small = dict(spec["config"]["model"], num_layers=1, d_model=32,
                 num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
                 vocab_size=64)
    spec["config"] = dict(spec["config"], model=small, reduced=list(small))
    spec["config"]["engine"] = dict(spec["config"]["engine"], num_pages=32,
                                    max_pages_per_seq=8)
    with pytest.raises(harness.Refused):
        run.go()
