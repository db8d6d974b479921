"""memlint (polykey_tpu/analysis/memory.py) tests: capacity-ledger
teeth (shrunk HBM, stale matrix), ML002 growth fixtures + the ring-cap
and annotation-strip teeth, knob-contract teeth against the REAL
DEPLOY.md / config.py / disagg_pool.py (deleting a row, dropping a
_config_env ship), heap-witness growth detection + the end-to-end
runtime witness, namespace isolation (PL/CL/ML never cross-fire,
per-tier baseline/prune isolation), the four-tier `all` aggregate, the
committed-artifact re-derivations (hostkv 1.606 footprint ratio, 8B
int8 hbm_weight_fraction), and the self-run gate asserting the repo is
clean under the committed-empty baseline."""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from polykey_tpu.analysis import concurrency, memory
from polykey_tpu.analysis.baseline import load_baseline
from polykey_tpu.analysis.cli import main as cli_main
from polykey_tpu.analysis.memory import (
    CONFIG_REL,
    DISAGG_REL,
    SERVED_MATRIX,
    check_capacity,
    check_knob_docs,
    check_knob_single_parse,
    check_ship_contract,
    module_env_reads,
    run_memlint,
    witness_findings,
)
from polykey_tpu.engine.roofline import CHIP_SPECS, grade, kv_pool_bytes_spec

REPO_ROOT = Path(__file__).resolve().parents[1]
MIB = 1 << 20


def memlint(tmp_path: Path, rel: str, source: str, only=None, deploy=""):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    if deploy is not None and not (tmp_path / "DEPLOY.md").exists():
        (tmp_path / "DEPLOY.md").write_text(deploy)
    findings, _ledgers = run_memlint(tmp_path, only=only)
    return findings


def blocking(findings, rule=None):
    return [f for f in findings if f.blocking
            and (rule is None or f.rule == rule)]


# -- registry / CLI surface ---------------------------------------------------


def test_rule_table_lists_the_rules(capsys):
    assert memory.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("ML000", "ML001", "ML002", "ML003", "ML004",
                    "ML005", "ML006"):
        assert rule_id in out


def test_only_typo_is_a_usage_error(capsys):
    assert memory.main(["--only", "ML999"]) == 2
    assert "unknown rule id" in capsys.readouterr().err


def test_only_refuses_prune_and_write_baseline(capsys):
    assert memory.main(["--only", "ML002", "--prune"]) == 2
    assert "full run" in capsys.readouterr().err
    assert memory.main(["--only", "ML002", "--write-baseline"]) == 2
    assert "full run" in capsys.readouterr().err


def test_prune_refuses_explicit_targets(tmp_path, capsys):
    (tmp_path / "polykey_tpu").mkdir()
    (tmp_path / "polykey_tpu" / "clean.py").write_text("x = 1\n")
    rc = memory.main(["--root", str(tmp_path), "--prune", "polykey_tpu"])
    assert rc == 2
    assert "full run" in capsys.readouterr().err


# -- ML001 capacity contracts -------------------------------------------------


def test_served_matrix_fits_its_chips():
    findings, ledgers = check_capacity()
    assert not blocking(findings)
    assert len(ledgers) == len(SERVED_MATRIX) == 5
    for entry in ledgers:
        assert entry["fits"], entry["name"]
        assert 0.0 < entry["hbm_fraction"] < 1.0
        # Resident decomposition is self-consistent.
        assert entry["resident_bytes"] == pytest.approx(
            entry["weights_bytes"] + entry["kv_pool_bytes"]
            + entry["kv_scale_pool_bytes"] + entry["draft_weights_bytes"]
            + entry["draft_kv_pool_bytes"])


def test_teeth_shrinking_hbm_below_ledger_fires_ml001():
    """Acceptance teeth: shrink ChipSpec.hbm_bytes under the ledger and
    every served entry's capacity contract must block."""
    small = {name: dataclasses.replace(spec, hbm_bytes=2.0 * 2**30)
             for name, spec in CHIP_SPECS.items()}
    findings, ledgers = check_capacity(chip_specs=small)
    hits = blocking(findings, "ML001")
    assert len(hits) == len(SERVED_MATRIX)
    assert all("capacity contract violated" in f.message for f in hits)
    assert not any(entry["fits"] for entry in ledgers)


def test_stale_matrix_entry_is_ml000():
    entry = dict(SERVED_MATRIX[0])
    entry["quantize_bits"] = 5            # validate() rejects
    findings, ledgers = check_capacity(matrix=[entry])
    hits = blocking(findings, "ML000")
    assert hits and "stale" in hits[0].message
    assert not ledgers


def test_int8_ledger_carries_scale_pool_and_spec_draft():
    _, ledgers = check_capacity()
    by_name = {entry["name"]: entry for entry in ledgers}
    assert by_name["llama3-8b-int8"]["kv_scale_pool_bytes"] > 0
    assert by_name["llama3-8b-bf16-tp4"]["kv_scale_pool_bytes"] == 0
    spec = by_name["gemma2-27b-int8-spec-tp4"]
    assert spec["draft_weights_bytes"] > 0
    assert "spec_decode" in spec["transient_bytes"]
    # Donation credit equals exactly the pool planes the executables
    # alias in place — what the peak would grow by if GL002's contract
    # broke.
    assert spec["donation_credit_bytes"] == pytest.approx(
        spec["kv_pool_bytes"] + spec["kv_scale_pool_bytes"]
        + spec["draft_kv_pool_bytes"])


# -- ML002 unbounded growth ---------------------------------------------------


UNCAPPED = """\
    import threading


    class Recorder:
        def __init__(self):
            self._lock = threading.Lock()
            self._events = []

        def note(self, event):
            with self._lock:
                self._events.append(event)
"""


def test_ml002_fires_on_uncapped_long_lived_container(tmp_path):
    findings = memlint(tmp_path, "polykey_tpu/obs/r.py", UNCAPPED,
                       only={"ML002"})
    hits = blocking(findings, "ML002")
    assert len(hits) == 1
    assert "Recorder._events" in hits[0].message


def test_teeth_removing_a_ring_cap_fires_ml002(tmp_path):
    """Acceptance teeth: a deque(maxlen=...) ring is clean; removing
    the cap makes the same class block."""
    ring = """\
        import threading
        from collections import deque


        class Ring:
            def __init__(self):
                self._lock = threading.Lock()
                self._events = deque(maxlen=512)

            def note(self, event):
                with self._lock:
                    self._events.append(event)
    """
    clean = memlint(tmp_path, "polykey_tpu/obs/ring.py", ring,
                    only={"ML002"})
    assert not blocking(clean)
    uncapped = ring.replace("deque(maxlen=512)", "deque()")
    findings = memlint(tmp_path, "polykey_tpu/obs/ring.py", uncapped,
                       only={"ML002"})
    assert blocking(findings, "ML002")


def test_ml002_discipline_paths_are_clean(tmp_path):
    findings = memlint(tmp_path, "polykey_tpu/obs/d.py", """\
        import threading


        class Capped:
            def __init__(self):
                self._lock = threading.Lock()
                self._by_key = {}
                self._seen = set()

            def put(self, k, v):
                with self._lock:
                    self._by_key[k] = v
                    if len(self._by_key) > 64:
                        self._by_key.clear()

            def mark(self, k):
                with self._lock:
                    self._seen.add(k)

            def unmark(self, k):
                with self._lock:
                    self._seen.discard(k)
    """, only={"ML002"})
    assert not blocking(findings)


def test_ml002_short_lived_class_is_clean(tmp_path):
    # No lock, no while-True, no Thread base: one-shot helper objects
    # may accumulate freely for their bounded lifetime.
    findings = memlint(tmp_path, "polykey_tpu/obs/s.py", """\
        class Collector:
            def __init__(self):
                self.rows = []

            def add(self, row):
                self.rows.append(row)
    """, only={"ML002"})
    assert not blocking(findings)


def test_ml002_module_level_container_fires(tmp_path):
    findings = memlint(tmp_path, "polykey_tpu/obs/m.py", """\
        _REGISTRY = {}


        def register(name, obj):
            _REGISTRY[name] = obj
    """, only={"ML002"})
    hits = blocking(findings, "ML002")
    assert hits and "_REGISTRY" in hits[0].message


def test_teeth_stripping_an_ml002_annotation_fails_the_gate(tmp_path):
    """Teeth: the repo's deliberate survivors are annotation-guarded —
    stripping one ML002 reason from analysis/witness.py must make
    memlint block again."""
    needle = "disable=ML002"
    source = (REPO_ROOT / "polykey_tpu" / "analysis" / "witness.py") \
        .read_text()
    assert needle in source
    stripped = "\n".join(
        line for line in source.splitlines() if needle not in line)
    target = tmp_path / "polykey_tpu" / "analysis" / "witness.py"
    target.parent.mkdir(parents=True)
    target.write_text(stripped)
    findings, _ = run_memlint(tmp_path, only={"ML002"})
    assert blocking(findings, "ML002")


# -- ML003 knob documentation -------------------------------------------------


def test_module_env_reads_sees_all_read_shapes():
    tree = ast.parse(textwrap.dedent("""\
        import os

        _K = "POLYKEY_CONST_KNOB"
        a = os.environ.get("POLYKEY_GET_KNOB", "")
        b = os.getenv("POLYKEY_GETENV_KNOB")
        c = os.environ["POLYKEY_SUBSCRIPT_KNOB"]
        d = os.environ.get(_K)


        def from_env():
            return _env_int("POLYKEY_HELPER_KNOB", 3)


        def ship(env):
            env["POLYKEY_SHIPPED_KNOB"] = "1"   # store: not a read
    """))
    knobs = {k for k, _l, _f in module_env_reads(tree)}
    assert knobs == {"POLYKEY_GET_KNOB", "POLYKEY_GETENV_KNOB",
                     "POLYKEY_SUBSCRIPT_KNOB", "POLYKEY_CONST_KNOB",
                     "POLYKEY_HELPER_KNOB"}


def test_teeth_deleting_a_deploy_row_fires_ml003():
    """Acceptance teeth: the REAL config.py knob set is documented by
    the REAL DEPLOY.md; deleting one row makes ML003 block."""
    deploy = (REPO_ROOT / "DEPLOY.md").read_text()
    config_tree = ast.parse((REPO_ROOT / CONFIG_REL).read_text())
    reads = {CONFIG_REL: module_env_reads(config_tree)}
    assert any(k == "POLYKEY_NUM_PAGES" for k, _l, _f in reads[CONFIG_REL])
    assert not blocking(check_knob_docs(reads, deploy))
    stripped = "\n".join(
        line for line in deploy.splitlines()
        if "`POLYKEY_NUM_PAGES`" not in line)
    fired = blocking(check_knob_docs(reads, stripped), "ML003")
    assert [f.snippet for f in fired] == ["POLYKEY_NUM_PAGES"]


def test_ml003_internal_annotation_suffices():
    reads = {"polykey_tpu/engine/faults.py":
             [("POLYKEY_FAULTS", 10, "from_env_spec")]}
    assert not blocking(check_knob_docs(reads, "no tables here"))


def test_ml003_family_row_documents_every_member_first_cell_only():
    deploy = textwrap.dedent("""\
        | Knob | Default | Meaning |
        |---|---|---|
        | `POLYKEY_TP` / `POLYKEY_DP` | 1 | mesh axes |

        Runbook prose mentioning `POLYKEY_PROSE_ONLY` and a later-cell
        | `POLYKEY_ROW` | set `POLYKEY_LATER_CELL` first | ... |
    """)
    docs = memory.deploy_documented_knobs(deploy)
    assert docs == {"POLYKEY_TP", "POLYKEY_DP", "POLYKEY_ROW"}
    reads = {"polykey_tpu/x.py": [("POLYKEY_PROSE_ONLY", 1, "f"),
                                  ("POLYKEY_LATER_CELL", 2, "f")]}
    fired = blocking(check_knob_docs(reads, deploy), "ML003")
    assert {f.snippet for f in fired} == {"POLYKEY_PROSE_ONLY",
                                          "POLYKEY_LATER_CELL"}


def test_missing_deploy_md_is_ml000():
    fired = check_knob_docs({}, None)
    assert fired and fired[0].rule == "ML000"
    assert "DEPLOY.md" in fired[0].message


# -- ML004 single parse site --------------------------------------------------


def test_ml004_second_parse_site_fires_harness_exempt():
    reads = {
        CONFIG_REL: [("POLYKEY_PAGE_SIZE", 10, "from_env")],
        "polykey_tpu/engine/engine.py": [("POLYKEY_PAGE_SIZE", 50, "loop")],
        "scripts/soak.py": [("POLYKEY_PAGE_SIZE", 5, "<module>")],
        "bench.py": [("POLYKEY_PAGE_SIZE", 7, "<module>")],
    }
    fired = blocking(check_knob_single_parse(reads), "ML004")
    assert [f.path for f in fired] == ["polykey_tpu/engine/engine.py"]
    assert "default drift" in fired[0].message


# -- ML005 ship contract ------------------------------------------------------


def test_teeth_dropping_a_config_env_ship_fires_ml005():
    """Acceptance teeth (the PR 15 bug class): the REAL from_env /
    _config_env pair is closed; deleting one ship line reopens it."""
    config_tree = ast.parse((REPO_ROOT / CONFIG_REL).read_text())
    disagg_src = (REPO_ROOT / DISAGG_REL).read_text()
    ship_line = '"POLYKEY_SLO": config.slo_policy,'
    assert ship_line in disagg_src
    assert not blocking(
        check_ship_contract(config_tree, ast.parse(disagg_src)))
    stripped = "\n".join(
        line for line in disagg_src.splitlines() if ship_line not in line)
    fired = blocking(
        check_ship_contract(config_tree, ast.parse(stripped)), "ML005")
    assert [f.snippet for f in fired] == ["POLYKEY_SLO"]
    assert "workers" in fired[0].message


def test_ml005_stale_exemption_is_ml000():
    config_tree = ast.parse(
        'import os\n\n\ndef from_env():\n'
        '    return os.environ.get("POLYKEY_A", "")\n')
    disagg_tree = ast.parse(
        'def _config_env(config):\n    return {"POLYKEY_A": "x"}\n')
    fired = check_ship_contract(
        config_tree, disagg_tree,
        exempt={"POLYKEY_GONE": "stale reason"})
    assert [f.rule for f in fired] == ["ML000"]
    assert "stale exemption" in fired[0].message


def test_ml005_spawn_pin_counts_as_shipped():
    config_tree = ast.parse(
        'import os\n\n\ndef from_env():\n'
        '    a = os.environ.get("POLYKEY_A", "")\n'
        '    b = os.environ.get("POLYKEY_B", "")\n'
        '    return a, b\n')
    disagg_tree = ast.parse(textwrap.dedent("""\
        def _config_env(config):
            return {"POLYKEY_A": "x"}


        def _spawn(env):
            env["POLYKEY_B"] = ""
    """))
    assert not blocking(
        check_ship_contract(config_tree, disagg_tree, exempt={}))


# -- ML006 heap witness -------------------------------------------------------


def _proc(series, pools=None, pid=7):
    cps = []
    for i, cur in enumerate(series):
        cp = {"label": f"cp{i}", "elapsed_s": float(i),
              "traced_current": cur, "traced_peak": cur,
              "top": [{"file": "polykey_tpu/engine/leaky.py:10",
                       "bytes": cur // 2, "blocks": 4}]}
        if pools is not None:
            cp["pools"] = pools
        cps.append(cp)
    return {"version": 1, "pid": pid, "argv0": "scripts/occupancy_soak.py",
            "checkpoints": cps, "dropped_checkpoints": 0}


def test_witness_sustained_growth_fires_with_sites():
    series = [10 * MIB, 40 * MIB, 60 * MIB, 100 * MIB, 110 * MIB,
              120 * MIB, 130 * MIB, 140 * MIB, 160 * MIB]
    fired = witness_findings([_proc(series)])
    assert len(fired) == 1
    assert fired[0].rule == "ML006"
    assert "leaky.py" in fired[0].message
    assert "pid 7" in fired[0].message


def test_witness_flat_and_warmup_only_growth_are_clean():
    flat = [100 * MIB] * 9
    # All growth inside the warmup prefix (model load, jit caches).
    warmup = [10 * MIB, 80 * MIB, 100 * MIB] + [101 * MIB] * 6
    assert not witness_findings([_proc(flat), _proc(warmup, pid=8)])


def test_witness_short_series_is_ignored():
    growing = [i * 64 * MIB for i in range(5)]   # < 6 checkpoints
    assert not witness_findings([_proc(growing)])


def test_witness_pool_above_declared_capacity_fires():
    pools = {"device_kv_pages": {"used": 150, "capacity": 142}}
    fired = witness_findings([_proc([100 * MIB] * 9, pools=pools)])
    assert len(fired) == 1
    assert "above its declared capacity" in fired[0].message
    assert fired[0].snippet == "device_kv_pages"


def test_runtime_witness_end_to_end(tmp_path):
    """POLYKEY_HEAP_WITNESS=1 arms tracemalloc at package import;
    labeled checkpoints with pool occupancy dump per-process JSON that
    `mem --witness` merges — the live half of the racelint-witness
    pattern."""
    out_dir = tmp_path / "wit"
    source = textwrap.dedent("""\
        import polykey_tpu  # noqa: F401  (arms the heap witness)
        from polykey_tpu.analysis import heapwitness

        assert heapwitness.installed()
        for i in range(8):
            heapwitness.checkpoint(
                f"cp{i}", pools={"p": {"used": i, "capacity": 100}})
        print(heapwitness.dump())
    """)
    env = dict(os.environ)
    env.update({
        "POLYKEY_HEAP_WITNESS": "1",
        "POLYKEY_HEAP_WITNESS_OUT": str(out_dir),
        "PYTHONPATH": str(REPO_ROOT),
    })
    proc = subprocess.run(
        [sys.executable, "-"], input=source, env=env,
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    from polykey_tpu.analysis import heapwitness

    merged = heapwitness.load_witness(str(out_dir))
    assert len(merged) == 1
    cps = merged[0]["checkpoints"]
    assert [cp["label"] for cp in cps] == [f"cp{i}" for i in range(8)]
    assert all(cp["traced_current"] > 0 for cp in cps)
    assert cps[3]["pools"]["p"] == {"used": 3, "capacity": 100}
    assert not witness_findings(merged)
    # And through the CLI gate the smoke jobs run.
    rc = memory.main(["--root", str(REPO_ROOT), "--only", "ML006",
                      "--witness", str(out_dir)])
    assert rc == 0


def test_witness_flag_off_means_not_installed_and_checkpoint_is_noop():
    from polykey_tpu.analysis import heapwitness

    if heapwitness.installed():        # another test armed it in-process
        pytest.skip("witness armed in this process")
    heapwitness.checkpoint("ignored")  # must not raise


# -- namespaces & baselines ---------------------------------------------------


SUPPRESSED_GROWTH = """\
    import threading


    class Sticky:
        def __init__(self):
            self._lock = threading.Lock()
            self._sticky = {}

        def note(self, k, v):
            with self._lock:
                # polylint: disable=ML002(EWMA per replica id: bounded by fleet size)
                self._sticky[k] = v
"""


def test_ml_suppression_silences_memlint_only(tmp_path):
    findings = memlint(tmp_path, "polykey_tpu/engine/e.py",
                       SUPPRESSED_GROWTH)
    assert not blocking(findings)
    assert any(f.suppressed and f.rule == "ML002" for f in findings)
    # racelint must neither honor nor complain about the ML namespace.
    race_findings, _ = concurrency.run_race(tmp_path)
    assert not blocking(race_findings)
    # polylint owns unowned-namespace complaints, and ML is owned.
    from polykey_tpu.analysis import check_file

    pl = check_file(tmp_path / "polykey_tpu" / "engine" / "e.py", tmp_path)
    assert not [f for f in pl if f.blocking and "ML002" in f.message]


def test_cl_suppressions_are_invisible_to_memlint(tmp_path):
    findings = memlint(tmp_path, "polykey_tpu/engine/q.py", """\
        def quiet():
            return 1  # polylint: disable=CL004(nothing blocks here)
    """)
    assert not blocking(findings)      # unused-CL is racelint's report


def test_unused_ml_suppression_is_ml000(tmp_path):
    findings = memlint(tmp_path, "polykey_tpu/engine/u.py", """\
        def quiet():
            return 1  # polylint: disable=ML002(nothing grows here)
    """)
    hits = blocking(findings, "ML000")
    assert hits and "unused suppression" in hits[0].message


def test_baseline_round_trip_and_per_tier_prune_isolation(tmp_path, capsys):
    """memlint and racelint each baseline their own namespace into
    their own file; pruning one tier never touches the other's debt."""
    (tmp_path / "DEPLOY.md").write_text("")
    pkg = tmp_path / "polykey_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "grow.py").write_text(textwrap.dedent(UNCAPPED))
    # A racelint-only escape: guarded writes, an unguarded alias leak —
    # disciplined for ML (len + clear) so the tiers don't overlap.
    (pkg / "escape.py").write_text(textwrap.dedent("""\
        import threading


        class E:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = {}

            def put(self, k, v):
                with self._lock:
                    self.items[k] = v
                    if len(self.items) > 64:
                        self.items.clear()

            def snapshot(self):
                return self.items
    """))
    root = str(tmp_path)
    assert memory.main(["--root", root]) == 1
    assert concurrency.main(["--root", root]) == 1
    capsys.readouterr()
    assert memory.main(["--root", root, "--write-baseline"]) == 0
    assert concurrency.main(["--root", root, "--write-baseline"]) == 0
    assert memory.main(["--root", root]) == 0
    assert concurrency.main(["--root", root]) == 0
    capsys.readouterr()
    mem_base = load_baseline(tmp_path / "memlint-baseline.json")
    race_base = load_baseline(tmp_path / "racelint-baseline.json")
    assert len(mem_base["findings"]) == 1
    assert len(race_base["findings"]) >= 1
    # Fix the memlint finding; mem --prune drops ONLY the ML entry.
    (pkg / "grow.py").write_text("x = 1\n")
    assert memory.main(["--root", root, "--prune"]) == 0
    assert "pruned 1 stale" in capsys.readouterr().out
    assert not load_baseline(tmp_path / "memlint-baseline.json")["findings"]
    assert load_baseline(
        tmp_path / "racelint-baseline.json") == race_base
    assert concurrency.main(["--root", root]) == 0


def test_json_output_shape(tmp_path, capsys):
    (tmp_path / "DEPLOY.md").write_text("")
    (tmp_path / "polykey_tpu").mkdir()
    (tmp_path / "polykey_tpu" / "clean.py").write_text("x = 1\n")
    assert memory.main(["--root", str(tmp_path), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["summary"]["mem_clean"] is True
    assert len(payload["ledger"]) == len(SERVED_MATRIX)
    for entry in payload["ledger"]:
        assert entry["fits"] is True
        assert 0 < entry["hbm_fraction"] < 1


# -- the five-tier `all` aggregate --------------------------------------------


def test_all_includes_memlint_and_any_tier_failure_fails(
        tmp_path, capsys, monkeypatch):
    from polykey_tpu.analysis import graph

    def fake_graph_main(argv):
        if "--json" in argv:
            print(json.dumps({"findings": [], "summary": {"blocking": 0}}))
        return 0

    monkeypatch.setattr(graph, "main", fake_graph_main)
    (tmp_path / "DEPLOY.md").write_text("")
    (tmp_path / "polykey_tpu").mkdir()
    (tmp_path / "polykey_tpu" / "clean.py").write_text("x = 1\n")
    rc = cli_main(["all", "--root", str(tmp_path), "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(payload["tiers"]) == {"polylint", "racelint", "graphlint",
                                     "memlint", "schedlint"}
    assert payload["summary"]["all_clean"] is True

    # A memlint-only failure (clean for every other tier) fails the
    # aggregate: an uncapped long-lived container is invisible to
    # PL/CL/GL.
    (tmp_path / "polykey_tpu" / "grow.py").write_text(
        textwrap.dedent(UNCAPPED))
    rc = cli_main(["all", "--root", str(tmp_path), "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["summary"]["all_clean"] is False
    assert payload["summary"]["exit_codes"]["memlint"] == 1
    assert payload["summary"]["exit_codes"]["polylint"] == 0
    assert payload["summary"]["exit_codes"]["racelint"] == 0


# -- committed capacity claims, re-derived ------------------------------------


def test_ledger_rederives_hostkv_footprint_ratio():
    """The hostkv soak's committed 1.606 host:device page ratio falls
    out of the soak's sizing identities applied to the artifact's
    recorded config — recomputed here, not restated — and the ledger's
    host-tier page math confirms the host pool absorbs the spill."""
    art = json.loads(
        (REPO_ROOT / "perf" / "hostkv_soak_2026-08-04.json").read_text())
    c = art["config"]
    page = c["page_size"]
    # max_seq = ceil((final + max_new + page)/page)*page, recorded both
    # sides, pins max_new without restating it.
    max_new = c["max_seq_len"] - c["final_history_tokens"] - page
    pages_per_session = -(-(c["final_history_tokens"] + max_new) // page)
    aggregate = c["sessions"] * pages_per_session
    num_pages = max(int(aggregate / 1.6) + 1, 3 * pages_per_session + 12)
    assert num_pages == c["num_pages"]
    assert aggregate == art["aggregate_kv_pages"]
    assert num_pages - 1 == art["device_pool_pages"]
    ratio = aggregate / (num_pages - 1)
    assert round(ratio, 3) == art["kv_footprint_ratio"]
    assert ratio > 1.5                   # genuinely oversubscribed

    from polykey_tpu.engine.config import EngineConfig

    cfg = dataclasses.replace(
        EngineConfig(), model=c["model"], dtype="float32",
        page_size=page, num_pages=c["num_pages"],
        max_seq_len=c["max_seq_len"], host_kv_bytes=c["host_kv_bytes"])
    ledger = memory.build_ledger(cfg, "tpu-v5e", 1)
    spill_pages = aggregate - (num_pages - 1)
    assert 0 < spill_pages <= ledger["host_capacity_pages"]
    assert ledger["host_kv_page_bytes"] * ledger["host_capacity_pages"] \
        <= c["host_kv_bytes"]


def test_ledger_rederives_8b_int8_weight_fraction():
    """The committed hbm_weight_fraction_8b_int8 (0.4674) is the
    ledger's weights_bytes over v5e HBM — grade() and the memlint
    ledger must both reproduce the artifact's number exactly."""
    art = json.loads(
        (REPO_ROOT / "perf" / "hostkv_soak_2026-08-04.json").read_text())
    committed = art["roofline"]["hbm_weight_fraction_8b_int8"]
    g = grade("llama-3-8b", "bfloat16", True, 8, "int8",
              tok_s=100.0, avg_lanes=8, avg_ctx=192,
              chip=CHIP_SPECS["tpu-v5e"])
    assert g["hbm_weight_fraction"] == committed
    _, ledgers = check_capacity()
    entry = next(l for l in ledgers if l["name"] == "llama3-8b-int8")
    assert round(entry["weights_bytes"] / entry["hbm_bytes_per_chip"],
                 4) == committed


def test_kv_pool_mirror_matches_allocator_byte_for_byte():
    """The ledger's stdlib pool arithmetic is a pure mirror of the jax
    allocator — pinned against the real arrays so they can't drift."""
    import jax
    import jax.numpy as jnp

    from polykey_tpu.engine import kv_cache
    from polykey_tpu.models.config import get_config

    mcfg = get_config("tiny-llama")
    for kv_dtype_str, kv_dtype in (("bfloat16", None), ("int8", jnp.int8)):
        pool = kv_cache.init_paged_kv(mcfg, 8, 16, jnp.bfloat16, kv_dtype)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(pool))
        assert kv_pool_bytes_spec(mcfg, 8, 16, kv_dtype_str) == nbytes
        assert nbytes == kv_cache.kv_pool_bytes(
            mcfg, 8, 16, jnp.bfloat16, kv_dtype)


# -- the repo itself ----------------------------------------------------------


def test_self_run_repo_is_clean_under_committed_baseline(capsys):
    """The acceptance gate: `python -m polykey_tpu.analysis mem` exits
    0 on this repo with the committed-empty baseline — every surfaced
    finding is fixed or reason-annotated."""
    rc = memory.main(["--root", str(REPO_ROOT)])
    out = capsys.readouterr().out
    assert rc == 0, f"memlint found blocking findings:\n{out}"


def test_committed_baseline_is_empty():
    data = load_baseline(REPO_ROOT / "memlint-baseline.json")
    assert data["findings"] == {}


def test_committed_heap_witness_artifact_is_growth_free():
    """The witnessed hostkv soak (supervised mid-run restart included)
    is a committed acceptance artifact: labeled checkpoints with pool
    occupancy, zero ML006 findings."""
    path = REPO_ROOT / "perf" / "heap_witness_hostkv_2026-08-07.json"
    report = json.loads(path.read_text())
    assert report["findings"] == []
    procs = report["processes"]
    assert procs
    labels = [cp["label"] for proc in procs
              for cp in proc["checkpoints"]]
    assert any(lab.startswith("hostkv-round") for lab in labels)
    assert "hostkv-post-restart" in labels
    assert "hostkv-final" in labels
    pooled = [cp for proc in procs for cp in proc["checkpoints"]
              if cp.get("pools")]
    assert pooled
    for cp in pooled:
        for name, pool in cp["pools"].items():
            assert pool["used"] <= pool["capacity"], (cp["label"], name)
