"""schedlint (polykey_tpu/analysis/sched.py) tests: a firing and a
non-firing fixture per SL rule (progress floor, cursor discipline,
frontier order, bounded wait, quota conservation), teeth against the
REAL engine.py (stripping the restore progress floor or the
starved-first re-anchor must re-block the gate), the starvation-witness
merge (multi-process dirs, version skew, the wait-age gate through the
CLI), SL-namespace suppression isolation, the stale-contract-anchor
SL000 surface, the shared-CLI-plumbing rc-2 surfaces, baseline
round-trip, the committed soak artifact's embedded verdict, and the
self-run gate asserting the repo is clean under the committed-empty
baseline."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from polykey_tpu.analysis import concurrency, sched, schedwitness
from polykey_tpu.analysis.baseline import load_baseline
from polykey_tpu.analysis.sched import (
    WITNESS_MAX_WAIT_AGE_S,
    run_sched,
    witness_findings,
    witness_verdict,
)

REPO_ROOT = Path(__file__).resolve().parents[1]
ENGINE = REPO_ROOT / "polykey_tpu" / "engine" / "engine.py"


def schedlint(tmp_path: Path, rel: str, source: str, only=None):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return run_sched(tmp_path, only=only)


def blocking(findings, rule=None):
    return [f for f in findings if f.blocking
            and (rule is None or f.rule == rule)]


# -- registry / CLI surface ---------------------------------------------------


def test_rule_table_lists_the_rules(capsys):
    assert sched.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("SL000", "SL001", "SL002", "SL003", "SL004", "SL006"):
        assert rule_id in out
    assert "SL005" not in out


def test_only_typo_is_a_usage_error(capsys):
    assert sched.main(["--only", "SL999"]) == 2
    assert "unknown rule id" in capsys.readouterr().err


def test_only_refuses_prune_and_write_baseline(capsys):
    assert sched.main(["--only", "SL002", "--prune"]) == 2
    assert "full run" in capsys.readouterr().err
    assert sched.main(["--only", "SL002", "--write-baseline"]) == 2
    assert "full run" in capsys.readouterr().err


def test_prune_refuses_explicit_targets(tmp_path, capsys):
    (tmp_path / "polykey_tpu").mkdir()
    (tmp_path / "polykey_tpu" / "clean.py").write_text("x = 1\n")
    rc = sched.main(["--root", str(tmp_path), "--prune", "polykey_tpu"])
    assert rc == 2
    assert "full run" in capsys.readouterr().err


def test_unloadable_witness_is_a_usage_error(tmp_path, capsys):
    rc = sched.main(["--witness", str(tmp_path / "absent.json")])
    assert rc == 2
    assert "cannot load witness" in capsys.readouterr().err


# -- SL001 progress floor -----------------------------------------------------


FLOORLESS = """\
    class Eng:
        def pump(self, items, budget):
            issued = 0
            for it in items:
                if issued >= budget:
                    break
                issued += 1
                self.emit(it)
"""


def test_sl001_budget_exit_without_floor_fires(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/a.py", FLOORLESS,
                         only={"SL001"})
    hits = blocking(findings, "SL001")
    assert len(hits) == 1
    assert "issued >= budget" in hits[0].message


def test_sl001_progress_conjunct_is_clean(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/b.py", """\
        class Eng:
            def pump(self, items, budget):
                issued = 0
                for it in items:
                    if issued >= budget and issued > 0:
                        break
                    issued += 1
                    self.emit(it)
    """, only={"SL001"})
    assert not blocking(findings, "SL001")


def test_sl001_grown_worklist_conjunct_is_clean(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/c.py", """\
        class Eng:
            def pump(self, items, chunk_quota):
                spent = 0
                ranges = []
                for it in items:
                    if spent >= chunk_quota and ranges:
                        break
                    ranges.append(it)
                    spent += it.width
                return ranges
    """, only={"SL001"})
    assert not blocking(findings, "SL001")


# -- SL002 cursor discipline --------------------------------------------------


def test_sl002_read_without_write_on_exit_path_fires(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/d.py", """\
        class Eng:
            def __init__(self):
                self._scan_rr = 0

            def pick(self, n):
                for off in range(n):
                    i = (self._scan_rr + off) % n
                    if self.ok(i):
                        return i
                return None
    """, only={"SL002"})
    hits = blocking(findings, "SL002")
    assert hits
    assert any("neither advances nor re-anchors" in f.message
               for f in hits)


def test_sl002_unbounded_advance_fires(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/e.py", """\
        class Eng:
            def __init__(self):
                self._scan_rr = 0

            def bump(self):
                self._scan_rr = self._scan_rr + 1
    """, only={"SL002"})
    hits = blocking(findings, "SL002")
    assert len(hits) == 1
    assert "without a modulo bound" in hits[0].message


def test_sl002_early_exit_sweep_without_reanchor_fires(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/f.py", """\
        class Eng:
            def __init__(self):
                self._scan_rr = 0

            def pick(self, n):
                for off in range(n):
                    i = (self._scan_rr + off) % n
                    if self.ok(i):
                        self._scan_rr = (i + 1) % n
                        return i
                self._scan_rr = (self._scan_rr + 1) % n
                return None
    """, only={"SL002"})
    hits = blocking(findings, "SL002")
    assert len(hits) == 1
    assert "never re-anchors" in hits[0].message


def test_sl002_reanchor_plus_advance_is_clean(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/g.py", """\
        class Eng:
            def __init__(self):
                self._scan_rr = 0

            def pick(self, n):
                for off in range(n):
                    i = (self._scan_rr + off) % n
                    if self.ok(i):
                        self._scan_rr = i
                        return i
                self._scan_rr = (self._scan_rr + 1) % n
                return None
    """, only={"SL002"})
    assert not blocking(findings, "SL002")


def test_sl002_rrcursor_helper_idiom_is_clean(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/h.py", """\
        class _RRCursor:
            def __init__(self):
                self.pos = 0

        class Eng:
            def __init__(self):
                self._queue_cursor = _RRCursor()

            def pick(self, n):
                for i in self._queue_cursor.scan(n):
                    if self.ok(i):
                        self._queue_cursor.reanchor(i)
                        return i
                self._queue_cursor.advance(n)
                return None
    """, only={"SL002"})
    assert not blocking(findings, "SL002")


# -- SL003 frontier ordering --------------------------------------------------


def test_sl003_inverted_frontier_order_fires(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/i.py", """\
        class Eng:
            def run(self):
                while not self._stop.is_set():
                    self._dispatch_step()
                    self._issue_restores()
    """, only={"SL003"})
    hits = blocking(findings, "SL003")
    assert len(hits) == 1
    assert "frontier order violated" in hits[0].message


def test_sl003_ordered_frontiers_are_clean(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/j.py", """\
        class Eng:
            def run(self):
                while not self._stop.is_set():
                    self._issue_restores()
                    self._advance_chunked_prefills()
                    self._dispatch_step()
    """, only={"SL003"})
    assert not blocking(findings, "SL003")


def test_sl003_missing_faulting_slot_guard_fires(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/k.py", """\
        class Eng:
            def _advance_chunked_prefills(self, width):
                for s in self.slots:
                    if s.pending is None:
                        continue
                    self.emit(s)

            def faulting(self, s):
                return s.restore_pages
    """, only={"SL003"})
    hits = blocking(findings, "SL003")
    assert len(hits) == 1
    assert "does not skip faulting slots" in hits[0].message


def test_sl003_guarded_builder_is_clean(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/l.py", """\
        class Eng:
            def _advance_chunked_prefills(self, width):
                for s in self.slots:
                    if s.pending is None:
                        continue
                    if s.restore_pages is not None:
                        continue
                    self.emit(s)
    """, only={"SL003"})
    assert not blocking(findings, "SL003")


# -- SL004 bounded wait -------------------------------------------------------


UNBOUNDED_QUEUE = """\
    import threading
    from collections import deque


    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self._inbox = deque()

        def drain(self):
            while self._inbox:
                item = self._inbox.popleft()
                self.handle(item)
"""


def test_sl004_unbounded_consumed_queue_fires(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/m.py",
                         UNBOUNDED_QUEUE, only={"SL004"})
    hits = blocking(findings, "SL004")
    assert len(hits) == 1
    assert "no admission bound" in hits[0].message


def test_sl004_bounded_ctor_shed_and_size_check_are_clean(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/n.py", """\
        import queue
        import threading
        from collections import deque


        class Server:
            def __init__(self):
                self._lock = threading.Lock()
                self._bounded = queue.Queue(maxsize=64)
                self._ringed = deque(maxlen=128)
                self._shedded = deque()
                self._sized = deque()

            def drain(self):
                self._bounded.get()
                self._ringed.popleft()

            def reap(self):
                item = self._shedded.popleft()
                if self.deadline_expired(item):
                    return None
                return item

            def admit_and_pop(self, item, cap):
                if len(self._sized) < cap:
                    self._sized.append(item)
                return self._sized.popleft()
    """, only={"SL004"})
    assert not blocking(findings, "SL004")


# -- teeth against the real engine -------------------------------------------


def _engine_copy(tmp_path: Path, old: str, new: str) -> Path:
    source = ENGINE.read_text()
    assert old in source, f"teeth anchor gone from engine.py: {old!r}"
    target = tmp_path / "polykey_tpu" / "engine" / "engine.py"
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(source.replace(old, new))
    return target


def test_teeth_stripping_the_restore_progress_floor_fires_sl001(tmp_path):
    """The SL001 fix this tier landed (`and issued > 0` on the restore
    budget exit) must be load-bearing: removing it re-blocks the gate."""
    _engine_copy(tmp_path,
                 "issued >= self._restore_slots and issued > 0",
                 "issued >= self._restore_slots")
    findings = run_sched(tmp_path, only={"SL001"})
    hits = blocking(findings, "SL001")
    assert len(hits) == 1
    assert "_restore_slots" in hits[0].message


def test_teeth_replacing_reanchor_with_advance_fires_sl002(tmp_path):
    """Always advancing past the anchor is fair in shape but hands the
    skipped slot's turn away — the starved-first re-anchor on the
    restore budget exit must be load-bearing."""
    _engine_copy(tmp_path,
                 "self._restore_rr.reanchor(i)",
                 "self._restore_rr.advance(i + 1)")
    findings = run_sched(tmp_path, only={"SL002"})
    hits = blocking(findings, "SL002")
    assert hits
    assert any("_restore_rr" in f.message
               and "never re-anchors" in f.message for f in hits)


def test_real_engine_is_clean_standalone(tmp_path):
    """The committed engine passes every SL rule on its own — the teeth
    fixtures above differ from green by exactly their one edit."""
    _engine_copy(tmp_path, "and issued > 0", "and issued > 0")
    assert not blocking(run_sched(tmp_path))


# -- SL000 stale contract anchors --------------------------------------------


def test_stale_contract_anchors_are_sl000(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/engine.py", """\
        x = 1
    """)
    hits = blocking(findings, "SL000")
    assert any("contract anchor" in f.message for f in hits)
    assert any("engine loop" in f.message for f in hits)
    names = {f.message.split("(")[0] for f in hits
             if "contract anchor" in f.message}
    assert len(names) == len(sched.ORDERED_FRONTIERS)


# -- SL006 witness merge ------------------------------------------------------


def _proc(pid=7, **frontiers):
    merged = {}
    for name, (age, skips) in frontiers.items():
        merged[name] = {
            "notes": 100, "serves": 50,
            "max_wait_age_s": age, "max_wait_slot": 3,
            "max_consecutive_skips": skips, "max_skip_slot": 3,
            "outstanding": [],
        }
    return {"version": 1, "pid": pid, "argv0": "t", "elapsed_s": 1.0,
            "frontiers": merged}


def test_witness_wait_age_over_gate_fires():
    fired = witness_findings([_proc(prefill=(9.0, 5))])
    assert len(fired) == 1
    assert fired[0].rule == "SL006"
    assert "prefill" in fired[0].message
    assert "9.000s" in fired[0].message
    assert not witness_findings([_proc(prefill=(1.0, 5))])


def test_witness_skip_count_over_gate_fires():
    fired = witness_findings([_proc(decode=(0.1, 200_000))])
    assert len(fired) == 1
    assert "200000 consecutive" in fired[0].message


def test_witness_verdict_aggregates_across_processes():
    verdict = witness_verdict([
        _proc(pid=1, prefill=(0.5, 3), restore=(0.1, 1)),
        _proc(pid=2, prefill=(2.0, 9)),
    ])
    assert verdict["processes"] == 2
    assert verdict["max_wait_age_s"] == 2.0
    assert verdict["frontiers"]["prefill"]["max_wait_age_s"] == 2.0
    assert verdict["frontiers"]["prefill"]["max_consecutive_skips"] == 9
    assert verdict["frontiers"]["prefill"]["notes"] == 200
    assert verdict["gate_max_wait_age_s"] == WITNESS_MAX_WAIT_AGE_S
    assert verdict["starvation_free"] is True
    assert verdict["findings"] == []
    tight = witness_verdict([_proc(prefill=(2.0, 9))],
                            max_wait_age_s=1.0)
    assert tight["starvation_free"] is False
    assert tight["gate_max_wait_age_s"] == 1.0
    assert tight["findings"]


def test_witness_dir_merge_and_version_skew(tmp_path):
    (tmp_path / "sched_witness_1.json").write_text(
        json.dumps(_proc(pid=1, decode=(0.1, 1))))
    (tmp_path / "sched_witness_2.json").write_text(
        json.dumps(_proc(pid=2, decode=(0.2, 2))))
    merged = schedwitness.load_witness(str(tmp_path))
    assert [p["pid"] for p in merged] == [1, 2]

    skewed = _proc(pid=3)
    skewed["version"] = 99
    (tmp_path / "sched_witness_3.json").write_text(json.dumps(skewed))
    with pytest.raises(ValueError, match="version"):
        schedwitness.load_witness(str(tmp_path))

    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(ValueError, match="no sched_witness_"):
        schedwitness.load_witness(str(empty))


def test_runtime_witness_end_to_end(tmp_path):
    """POLYKEY_SCHED_WITNESS=1 arms the recorder at package import;
    note() calls at dispatch boundaries dump per-process JSON that
    `sched --witness` merges and gates — the live half of the
    lock/heap-witness pattern."""
    out_dir = tmp_path / "wit"
    source = textwrap.dedent("""\
        import time

        import polykey_tpu  # noqa: F401  (arms the sched witness)
        from polykey_tpu.analysis import schedwitness

        assert schedwitness.installed()
        schedwitness.note("prefill", [0], [1, 2])
        time.sleep(0.05)
        schedwitness.note("prefill", [1], [2])
        schedwitness.note("decode", [0, 1, 2], [])
        print(schedwitness.dump())
    """)
    env = dict(os.environ)
    env.update({
        "POLYKEY_SCHED_WITNESS": "1",
        "POLYKEY_SCHED_WITNESS_OUT": str(out_dir),
        "PYTHONPATH": str(REPO_ROOT),
    })
    proc = subprocess.run(
        [sys.executable, "-"], input=source, env=env,
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    merged = schedwitness.load_witness(str(out_dir))
    assert len(merged) == 1
    prefill = merged[0]["frontiers"]["prefill"]
    assert prefill["notes"] == 2
    assert prefill["serves"] == 2
    # Slot 2 was skipped at both boundaries: its age spans the sleep.
    assert prefill["max_skip_slot"] == 2
    assert prefill["max_consecutive_skips"] == 2
    assert 0.04 <= prefill["max_wait_age_s"] < 5.0
    assert merged[0]["frontiers"]["decode"]["serves"] == 3
    assert not witness_findings(merged)
    # Through the CLI gate the smoke jobs run — and the gate has teeth:
    # the same dump fails under a wait-age gate tighter than the sleep.
    rc = sched.main(["--root", str(REPO_ROOT), "--only", "SL006",
                     "--witness", str(out_dir)])
    assert rc == 0
    rc = sched.main(["--root", str(REPO_ROOT), "--only", "SL006",
                     "--witness", str(out_dir),
                     "--max-wait-age", "0.001"])
    assert rc == 1


def test_witness_flag_off_means_not_installed_and_note_is_noop():
    if schedwitness.installed():       # another test armed it in-process
        pytest.skip("witness armed in this process")
    schedwitness.note("decode", [0], [1])    # must not raise
    assert schedwitness.dump() is None
    assert schedwitness.snapshot()["frontiers"] == {}


# -- namespaces, suppressions & baselines ------------------------------------


def test_sl_suppression_silences_schedlint_only(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/s.py", """\
        from collections import deque


        class Server:
            def __init__(self):
                # polylint: disable=SL004(drained whole every tick: bounded by arrival window)
                self._inbox = deque()

            def serve_forever(self):
                while True:
                    if self._inbox:
                        self.handle(self._inbox.popleft())
    """)
    assert not blocking(findings)
    assert any(f.suppressed and f.rule == "SL004" for f in findings)
    # racelint must neither honor nor complain about the SL namespace.
    race_findings, _ = concurrency.run_race(tmp_path)
    assert not blocking(race_findings)


def test_unused_sl_suppression_is_sl000(tmp_path):
    findings = schedlint(tmp_path, "polykey_tpu/engine/t.py", """\
        def quiet():
            return 1  # polylint: disable=SL002(nothing rotates here)
    """)
    hits = blocking(findings, "SL000")
    assert hits and "unused suppression" in hits[0].message


def test_baseline_round_trip_and_prune(tmp_path, capsys):
    pkg = tmp_path / "polykey_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "w.py").write_text(textwrap.dedent(UNBOUNDED_QUEUE))
    root = str(tmp_path)
    assert sched.main(["--root", root]) == 1
    capsys.readouterr()
    assert sched.main(["--root", root, "--write-baseline"]) == 0
    base = load_baseline(tmp_path / "schedlint-baseline.json")
    assert len(base["findings"]) == 1
    assert sched.main(["--root", root]) == 0      # grandfathered
    out = capsys.readouterr().out
    assert "baselined" in out
    # Fix the debt: the entry goes stale, prune drops it.
    (pkg / "w.py").write_text("x = 1\n")
    assert sched.main(["--root", root]) == 0
    assert "stale baseline" in capsys.readouterr().out
    assert sched.main(["--root", root, "--prune"]) == 0
    base = load_baseline(tmp_path / "schedlint-baseline.json")
    assert base["findings"] == {}


def test_json_output_shape(tmp_path, capsys):
    (tmp_path / "polykey_tpu").mkdir()
    (tmp_path / "polykey_tpu" / "clean.py").write_text("x = 1\n")
    (tmp_path / "wit").mkdir()
    (tmp_path / "wit" / "sched_witness_9.json").write_text(
        json.dumps(_proc(pid=9, decode=(0.2, 4))))
    rc = sched.main(["--root", str(tmp_path), "--json",
                     "--witness", str(tmp_path / "wit")])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["summary"]["sched_clean"] is True
    assert payload["summary"]["witness_processes"] == 1
    assert payload["witness_verdict"]["starvation_free"] is True
    assert payload["witness_verdict"]["max_wait_age_s"] == 0.2


# -- the repo itself ----------------------------------------------------------


def test_self_run_repo_is_clean_under_committed_baseline(capsys):
    """The acceptance gate: `python -m polykey_tpu.analysis sched`
    exits 0 on this repo with the committed-empty baseline — every
    surfaced finding is fixed or reason-annotated."""
    rc = sched.main(["--root", str(REPO_ROOT)])
    out = capsys.readouterr().out
    assert rc == 0, f"schedlint found blocking findings:\n{out}"


def test_committed_baseline_is_empty():
    data = load_baseline(REPO_ROOT / "schedlint-baseline.json")
    assert data["findings"] == {}


def test_committed_soak_artifact_carries_starvation_verdict():
    """The witnessed occupancy soak is a committed acceptance artifact:
    the merged verdict rides the perf JSON, starvation-free with a
    bounded max wait-age."""
    path = REPO_ROOT / "perf" / "occupancy_soak_sched_witness_2026-08-07.json"
    art = json.loads(path.read_text())
    verdict = art["sched_witness"]
    assert verdict["starvation_free"] is True
    assert verdict["findings"] == []
    assert verdict["processes"] >= 1
    assert 0.0 <= verdict["max_wait_age_s"] <= verdict["gate_max_wait_age_s"]
    served = {name for name, st in verdict["frontiers"].items()
              if st["serves"] > 0}
    assert "decode" in served
    assert "prefill" in served
