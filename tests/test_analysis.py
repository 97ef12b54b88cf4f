"""branchlint conformance: every rule catches its golden violation and
passes its golden conforming twin, suppressions and the baseline round-
trip, the JSON schema is stable, and the repo self-hosts clean.

The fixtures are the rule catalogue in executable form (DESIGN §15):
each BL00x pair is the minimal program that separates "speaks the
branch-context protocol" from "silently breaks it".
"""

import json
import textwrap

import pytest

from repro.analysis import (
    RULES,
    analyze_paths,
    apply_baseline,
    load_baseline,
    render_json,
    write_baseline,
)
from repro.analysis.__main__ import main as lint_main


def check(tmp_path, source, rules=None, filename="snippet.py"):
    """Analyze one fixture snippet; returns the findings list."""
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return analyze_paths([str(f)], rules=rules)


# ---------------------------------------------------------------------------
# golden fixtures: one violating + one conforming program per rule
# ---------------------------------------------------------------------------

BL001_BAD = """
    from repro.core.errors import BranchError

    def http_handler(work):
        try:
            work()
        except Exception:
            pass

    def reject():
        raise RuntimeError("no errno crosses the wire")
"""

BL001_GOOD = """
    from repro.core.errors import BranchError, Errno

    def http_handler(work):
        try:
            work()
        except BranchError:
            pass    # errno already mapped by the caller

    def rethrow(work):
        try:
            work()
        except Exception as err:
            raise BranchError(str(err), errno=Errno.EINVAL)

    def reject():
        raise BranchError("mapped failure", errno=Errno.EINVAL)

    def bad_args(n):
        if n < 0:
            raise ValueError("python-contract error stays legal")
"""

BL002_BAD = """
    def peek_then_bail(session):
        hd = session.open([1, 2], 4)
        if session.admitted(hd):
            return True          # leak: hd still held on this exit
        session.close(hd)
        return False
"""

BL002_GOOD = """
    def balanced(session):
        hd = session.open([1, 2], 4)
        if session.admitted(hd):
            session.finish(hd)
            return True
        session.close(hd)
        return False

    def escapes(session):
        hd = session.open([1, 2], 4)
        return hd                # ownership transferred to the caller

    def vector(session, root):
        kids = session.branch(root, n=4)
        for hd in kids:          # iterated into per-element processing
            session.abort(hd)
"""

BL003_BAD = """
    async def handler(session):
        return session.commit(3)

    def feed(fut):
        fut.set_result(1)
"""

BL003_GOOD = """
    async def handler(mux):
        return await mux.call(lambda session: session.commit(3))

    async def poster(session):
        def on_engine():
            session.commit(3)    # closure shipped to the engine thread
        return on_engine

    def feed(loop, fut):
        def deliver():
            fut.set_result(1)
        loop.call_soon_threadsafe(deliver)
"""

BL004_BAD = """
    def unbalanced(tr, cond):
        tr.begin_span(1, "explore")
        if cond:
            return None          # exits with the span still open
        tr.end_span(1)
"""

BL004_GOOD = """
    def balanced(tr, work):
        tr.begin_span(1, "explore")
        try:
            return work()
        finally:
            tr.end_span(1)       # raise paths balance too
"""

BL005_BAD = """
    def setup(m, engine):
        m.counter("UndottedName").inc()
        cb = lambda: m.gauge("kv.level").set(engine.depth)
        return cb
"""

BL005_GOOD = """
    def setup(m, depth):
        m.counter("kv.commits").inc()
        m.gauge("kv.level").set(depth)   # set at the mutation site
        m.histogram("engine.fork_us").observe(12.0)
"""

BL006_BAD = """
    from repro.api.flags import BR_HOLD
    from repro.core.runtime_api import BR_KV

    def fork(session, root):
        word = BR_HOLD | BR_KV           # API and runtime words mixed
        kids = session.branch(root, BR_SPECULATE, 2)   # typo flag
        return kids

    def rewrite(session, hd):
        session.truncate(hd, 3)          # never mentions the gate
"""

BL006_GOOD = """
    from repro.api.flags import BR_HOLD, BR_SPECULATIVE
    from repro.core.runtime_api import BR_KV, BR_STATE

    def fork(session, root):
        return session.branch(root, BR_HOLD | BR_SPECULATIVE, 2)

    def runtime_word():
        return BR_STATE | BR_KV          # one namespace only

    def rewrite(session, hd):
        # opened BR_SPECULATIVE upstream: the gate is referenced here
        session.truncate(hd, 3)
"""

GOLDEN = {
    "BL001": (BL001_BAD, BL001_GOOD, 2),
    "BL002": (BL002_BAD, BL002_GOOD, 1),
    "BL003": (BL003_BAD, BL003_GOOD, 2),
    "BL004": (BL004_BAD, BL004_GOOD, 1),
    "BL005": (BL005_BAD, BL005_GOOD, 2),
    "BL006": (BL006_BAD, BL006_GOOD, 3),
}


@pytest.mark.parametrize("code", sorted(GOLDEN))
def test_rule_catches_golden_violation(tmp_path, code):
    bad, _good, n_expected = GOLDEN[code]
    result = check(tmp_path, bad, rules=[code])
    assert len(result.findings) == n_expected, \
        f"{code} found {[f.message for f in result.findings]}"
    for f in result.findings:
        assert f.rule == code
        assert f.line > 0 and f.snippet
        assert f.message


@pytest.mark.parametrize("code", sorted(GOLDEN))
def test_rule_passes_golden_conforming(tmp_path, code):
    _bad, good, _n = GOLDEN[code]
    result = check(tmp_path, good, rules=[code])
    assert result.findings == [], \
        f"{code} false positives: {[f.message for f in result.findings]}"


def test_bl005_reads_a_span_as_the_histogram_it_registers(tmp_path):
    result = check(tmp_path, """
        def setup(obs, m):
            m.counter("engine.decode_step_us").inc()
            with obs.span("engine.decode_step", rows=4):
                pass
            with obs.span("Undotted"):
                pass
            with obs.span("engine.decode_prep"):
                pass
            m.histogram("engine.decode_prep_us").observe(1.0)
    """, rules=["BL005"])
    msgs = [f.message for f in result.findings]
    assert len(msgs) == 2, msgs
    # the span's histogram collides with a counter of the same name
    clash = [m for m in msgs if "'engine.decode_step_us'" in m]
    assert len(clash) == 1
    assert "histogram" in clash[0] and "counter" in clash[0]
    # and its name is held to the dotted grammar
    assert any("'Undotted_us'" in m and "grammar" in m for m in msgs)


def test_all_six_rules_registered():
    assert sorted(RULES) == [f"BL00{i}" for i in range(1, 7)]
    for code, rule in RULES.items():
        assert rule.code == code and rule.title and rule.rationale


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_inline_suppression_silences_named_rule(tmp_path):
    result = check(tmp_path, """
        from repro.core.errors import BranchError

        def reject():
            raise RuntimeError("known debt")  # branchlint: ignore[BL001]
    """)
    assert result.findings == []
    assert result.suppressed == 1


def test_comment_line_above_suppresses_next_line(tmp_path):
    result = check(tmp_path, """
        from repro.core.errors import BranchError

        def reject():
            # branchlint: ignore[BL001]
            raise RuntimeError("known debt")
    """)
    assert result.findings == []
    assert result.suppressed == 1


def test_bare_ignore_suppresses_every_rule_on_that_line(tmp_path):
    result = check(tmp_path, """
        from repro.core.errors import BranchError

        def reject():
            raise RuntimeError("x")  # branchlint: ignore
    """)
    assert result.findings == [] and result.suppressed == 1


def test_suppression_of_other_rule_does_not_apply(tmp_path):
    result = check(tmp_path, """
        from repro.core.errors import BranchError

        def reject():
            raise RuntimeError("x")  # branchlint: ignore[BL004]
    """)
    assert [f.rule for f in result.findings] == ["BL001"]
    assert result.suppressed == 0


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_absorbs_then_survives_line_drift(tmp_path):
    src = tmp_path / "legacy.py"
    src.write_text(textwrap.dedent(BL001_BAD))
    result = analyze_paths([str(src)], rules=["BL001"])
    assert len(result.findings) == 2

    baseline_path = tmp_path / "baseline.json"
    write_baseline(result.findings, baseline_path)
    entries = load_baseline(baseline_path)
    new, absorbed = apply_baseline(result.findings, entries)
    assert new == [] and absorbed == 2

    # unrelated edits above the findings shift the lines; matching is
    # content-anchored so the baseline still absorbs them
    src.write_text("# a new header comment\nimport os  # noqa\n"
                   + textwrap.dedent(BL001_BAD))
    drifted = analyze_paths([str(src)], rules=["BL001"])
    new, absorbed = apply_baseline(drifted.findings, entries)
    assert new == [] and absorbed == 2

    # a genuinely new finding is NOT absorbed
    src.write_text(textwrap.dedent(BL001_BAD)
                   + "\ndef more():\n    raise RuntimeError('new')\n")
    grown = analyze_paths([str(src)], rules=["BL001"])
    new, absorbed = apply_baseline(grown.findings, entries)
    assert absorbed == 2
    assert len(new) == 1 and "new" in new[0].snippet


def test_baseline_entry_absorbs_at_most_one_finding(tmp_path):
    src = tmp_path / "dup.py"
    src.write_text(textwrap.dedent("""
        from repro.core.errors import BranchError

        def a():
            raise RuntimeError("same text")

        def b():
            raise RuntimeError("same text")
    """))
    result = analyze_paths([str(src)], rules=["BL001"])
    assert len(result.findings) == 2
    new, absorbed = apply_baseline(result.findings,
                                   [result.findings[0].to_json()])
    assert absorbed == 1 and len(new) == 1   # count-aware, not keyed-set


# ---------------------------------------------------------------------------
# output schema + CLI exit codes
# ---------------------------------------------------------------------------

def test_json_output_schema(tmp_path):
    result = check(tmp_path, BL004_BAD)
    doc = json.loads(render_json(result, result.findings, 0))
    assert doc["version"] == 1 and doc["tool"] == "branchlint"
    assert sorted(doc["rules"]) == sorted(RULES)
    for key in ("files_checked", "suppressed", "baselined",
                "parse_errors", "findings"):
        assert key in doc
    (finding,) = doc["findings"]
    assert set(finding) == {"file", "line", "col", "rule", "message",
                            "snippet"}
    assert finding["rule"] == "BL004"


def test_cli_red_on_injected_violation_green_when_fixed(tmp_path, capsys):
    """The lint-smoke contract: exit 1 on a non-baselined finding, exit
    0 once it is fixed — exactly what turns the CI job red."""
    bad = tmp_path / "injected.py"
    bad.write_text(textwrap.dedent(BL002_BAD))
    assert lint_main(["--no-baseline", "--format", "json",
                      str(bad)]) == 1
    out = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in out["findings"]] == ["BL002"]

    bad.write_text(textwrap.dedent(BL002_GOOD))
    assert lint_main(["--no-baseline", str(bad)]) == 0


def test_cli_exit_codes_usage_and_baseline_flow(tmp_path, capsys):
    bad = tmp_path / "legacy.py"
    bad.write_text(textwrap.dedent(BL001_BAD))
    assert lint_main(["--rules", "NOPE", str(bad)]) == 2

    baseline = tmp_path / "b.json"
    assert lint_main(["--write-baseline", str(baseline), str(bad)]) == 0
    assert lint_main(["--baseline", str(baseline), str(bad)]) == 0
    capsys.readouterr()
    assert lint_main(["--no-baseline", str(bad)]) == 1


def test_parse_error_reported_and_fails(tmp_path, capsys):
    broken = tmp_path / "broken.py"
    broken.write_text("def (:\n")
    assert lint_main(["--no-baseline", str(broken)]) == 1
    assert "parse error" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# self-hosting smoke: the shipped tree is clean against the committed
# baseline — the acceptance bar for `python -m repro.analysis src`
# ---------------------------------------------------------------------------

def test_selfhost_shipped_tree_is_clean():
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    result = analyze_paths([str(root / "src" / "repro")])
    baseline_file = root / ".branchlint-baseline.json"
    entries = load_baseline(baseline_file) if baseline_file.exists() \
        else []
    new, _absorbed = apply_baseline(result.findings, entries)
    assert result.parse_errors == []
    assert new == [], "\n".join(
        f"{f.file}:{f.line}: {f.rule} {f.message}" for f in new)
    assert result.files_checked > 100    # it really walked the tree


def test_selfhost_analysis_package_has_no_suppressions():
    """The checker must not exempt itself: zero branchlint suppression
    comments inside src/repro/analysis/ (acceptance criterion).  The
    scan is tokenizer-based so docstrings/regex literals that *mention*
    the grammar don't count — only comments the engine would honor."""
    import io
    import tokenize
    from pathlib import Path

    from repro.analysis.engine import _SUPPRESS_RE

    pkg = Path(__file__).resolve().parents[1] / "src" / "repro" / \
        "analysis"
    offenders = []
    for py in sorted(pkg.rglob("*.py")):
        toks = tokenize.generate_tokens(
            io.StringIO(py.read_text()).readline)
        for tok in toks:
            if tok.type == tokenize.COMMENT and \
                    _SUPPRESS_RE.search(tok.string):
                offenders.append(f"{py.name}:{tok.start[0]}")
    assert offenders == []
