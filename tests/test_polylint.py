"""polylint (polykey_tpu/analysis) tests: one firing and one non-firing
fixture per rule, suppression + baseline round-trips, CLI exit codes,
and the self-run gate asserting the repo itself is clean under the
committed baseline."""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import pytest

from polykey_tpu.analysis import all_rules, check_file, run_paths
from polykey_tpu.analysis.baseline import (
    apply_baseline,
    load_baseline,
    write_baseline,
)
from polykey_tpu.analysis.cli import main

REPO_ROOT = Path(__file__).resolve().parents[1]


def lint(tmp_path: Path, rel: str, source: str):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return check_file(path, tmp_path)


def blocking(findings, rule=None):
    return [f for f in findings if f.blocking
            and (rule is None or f.rule == rule)]


# -- registry ----------------------------------------------------------------


def test_registry_has_the_eight_rules():
    ids = [r.id for r in all_rules()]
    assert ids == sorted(ids)
    for expected in ("PL001", "PL002", "PL003", "PL004",
                     "PL005", "PL006", "PL007", "PL008"):
        assert expected in ids


# -- PL001 host-sync-in-hot-path ---------------------------------------------


def test_pl001_fires_on_sync_in_hot_function(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/hot.py", """\
        import numpy as np

        def _process_step(self, data):
            packed = np.asarray(data)
            return packed
    """)
    assert blocking(findings, "PL001")


def test_pl001_int_over_device_handle_fires(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/hot.py", """\
        def _resolve_slot(self, slot):
            return int(slot.token_dev)
    """)
    assert blocking(findings, "PL001")


def test_pl001_ignores_cold_functions_and_other_packages(tmp_path):
    cold = lint(tmp_path, "polykey_tpu/engine/cold.py", """\
        import numpy as np

        def prepare_request(self, ids):
            return np.asarray(ids, dtype=np.int32)
    """)
    assert not blocking(cold, "PL001")
    gateway = lint(tmp_path, "polykey_tpu/gateway/any.py", """\
        import numpy as np

        def _process_step(self, data):
            return np.asarray(data)
    """)
    assert not blocking(gateway, "PL001")


# -- PL002 wall-clock-for-durations ------------------------------------------


def test_pl002_fires_on_wall_clock_subtraction(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/obs/t.py", """\
        import time

        def f(start):
            t0 = time.time()
            direct = time.time() - start
            via_name = time.monotonic() - t0
            return direct, via_name
    """)
    assert len(blocking(findings, "PL002")) == 2


def test_pl002_allows_stamping(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/obs/t.py", """\
        import time

        def f():
            event = {"time": time.time()}
            dur = time.monotonic() - time.monotonic()
            return event, dur
    """)
    assert not blocking(findings, "PL002")


# -- PL003 silent-except ------------------------------------------------------


def test_pl003_fires_on_silent_swallow(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/gateway/x.py", """\
        def f(g):
            try:
                g()
            except Exception:
                pass
    """)
    assert blocking(findings, "PL003")


def test_pl003_satisfied_by_log_use_raise_or_comment(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/gateway/x.py", """\
        def f(g, logger, out):
            try:
                g()
            except Exception as e:
                out.put(("error", str(e)))
            try:
                g()
            except Exception:
                logger.error("g failed")
            try:
                g()
            except Exception:
                raise RuntimeError("wrapped")
            try:
                g()
            except Exception:
                # justification: g is best-effort prefetch, failure is benign
                pass
    """)
    assert not blocking(findings, "PL003")


def test_pl003_suppression_comment_is_not_a_justification(tmp_path):
    # A polylint suppression for another rule must not double as the
    # PL003 justification comment.
    findings = lint(tmp_path, "polykey_tpu/gateway/x.py", """\
        def f(g):
            try:
                g()
            except Exception:
                x = 1  # polylint: disable=PL999(not a justification)
    """)
    assert blocking(findings, "PL003")


# -- PL004 blocking-call-under-lock ------------------------------------------


def test_pl004_fires_on_sleep_and_queue_wait_under_lock(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/l.py", """\
        import time

        class C:
            def f(self):
                with self._lock:
                    time.sleep(0.1)
                    item = self._submit.get(timeout=1)
                return item
    """)
    assert len(blocking(findings, "PL004")) == 2


def test_pl004_allows_dict_get_and_waits_outside(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/l.py", """\
        import time

        class C:
            def f(self, key):
                with self._lock:
                    value = self._values.get(key, 0)
                time.sleep(0.1)
                return value
    """)
    assert not blocking(findings, "PL004")


# -- PL005 thread-hygiene -----------------------------------------------------


def test_pl005_fires_on_unowned_thread(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/gateway/t.py", """\
        import threading

        def f(work):
            t = threading.Thread(target=work)
            t.start()
    """)
    assert blocking(findings, "PL005")


def test_pl005_allows_daemon_or_joined_threads(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/gateway/t.py", """\
        import threading

        class Owner:
            def start(self, work):
                self._t = threading.Thread(target=work)
                self._t.start()

            def stop(self):
                self._t.join(timeout=5)

        def fire_and_forget(work):
            threading.Thread(target=work, daemon=True).start()

        def pool(work):
            threads = [threading.Thread(target=work, daemon=True)
                       for _ in range(4)]
            for t in threads:
                t.join()
    """)
    assert not blocking(findings, "PL005")


# -- PL006 jit-boundary purity ------------------------------------------------


def test_pl006_fires_on_impure_jit_functions(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/models/j.py", """\
        import jax
        import time
        from functools import partial

        @partial(jax.jit, static_argnames=("cfg",))
        def stamped(cfg, x):
            return x * time.time()

        def _closes(x):
            return x + self.scale

        handle = jax.jit(_closes)
    """)
    msgs = [f.message for f in blocking(findings, "PL006")]
    assert any("time.time" in m for m in msgs)
    assert any("self" in m for m in msgs)


def test_pl006_donated_buffer_must_be_reassigned(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/j.py", """\
        import jax

        def _step(params, pool, x):
            return x, pool

        class Engine:
            def setup(self):
                self._jit_step = jax.jit(_step, donate_argnames=("pool",))

            def bad(self):
                out, _ = self._jit_step(self.params, self.pool, 1)
                return out

            def good(self):
                out, self.pool = self._jit_step(self.params, self.pool, 1)
                return out
    """)
    hits = blocking(findings, "PL006")
    assert len(hits) == 1
    assert "self.pool" in hits[0].message


def test_pl006_clean_on_pure_jit(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/models/j.py", """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def double(x):
            return jnp.add(x, x)
    """)
    assert not blocking(findings, "PL006")


# -- PL007 prometheus-naming --------------------------------------------------


def test_pl007_fires_on_bad_family_names(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/obs/m.py", """\
        def collect(registry, hist):
            registry.counter("polykey_requests", "missing total suffix")
            registry.gauge("PolykeyDepth", "not snake case")
            lines = render_histogram("polykey_ttft", "no unit", hist)
            return lines
    """)
    assert len(blocking(findings, "PL007")) == 3


def test_pl007_accepts_obs_contract_names(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/obs/m.py", """\
        def collect(registry, obs, hist):
            from polykey_tpu.obs import Counter
            registry.counter("polykey_rpcs_total", "ok")
            registry.gauge("polykey_queue_depth", "ok")
            obs.registry.get_or_create(Counter, "polykey_stalls_total", "ok")
            return render_histogram("polykey_ttft_ms", "ok", hist)
    """)
    assert not blocking(findings, "PL007")


# -- PL008 dispatch-side-sync -------------------------------------------------


def test_pl008_fires_through_the_call_graph(tmp_path):
    """A sync hidden in an innocuously-named helper still fires when the
    helper is reachable from _dispatch_step — the closure PL001's name
    match can't see."""
    findings = lint(tmp_path, "polykey_tpu/engine/pipe.py", """\
        import numpy as np

        class E:
            def _dispatch_step(self):
                self._prepare()
                return self._jit(self._dev)

            def _prepare(self):
                # Innocuous name: PL001's ^_?(dispatch|...) misses it.
                return np.asarray(self._dev["tokens"])
    """)
    hits = blocking(findings, "PL008")
    assert hits and "_prepare" in hits[0].message
    assert "reachable from the dispatch side" in hits[0].message


def test_pl008_fires_in_upload_slot_state_root(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/pipe.py", """\
        def _upload_slot_state(self):
            self._dev["tokens"].block_until_ready()
    """)
    assert blocking(findings, "PL008")


def test_pl008_ignores_process_side_and_unreachable(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/pipe.py", """\
        import numpy as np

        class E:
            def _dispatch_step(self):
                return self._jit(self._dev)

            def _process_step(self, block):
                # polylint: disable=PL001(block resolve point)
                return np.asarray(block)

            def _unreachable_helper(self, data):
                return np.asarray(data)
    """)
    assert not blocking(findings, "PL008")


def test_pl008_cross_object_call_does_not_pull_local_namesake(tmp_path):
    """self.metrics.on_dispatch(...) is another object's method; a local
    function that happens to share the name must not join the dispatch
    closure (its legitimate process-side sync is not a finding)."""
    findings = lint(tmp_path, "polykey_tpu/engine/pipe.py", """\
        import numpy as np

        class E:
            def _dispatch_step(self):
                self.metrics.on_dispatch(1, 2)
                return self._jit(self._dev)

        def on_dispatch(block, _):
            # Module-level namesake, process-side by construction.
            return np.asarray(block)
    """)
    assert not blocking(findings, "PL008")


def test_pl008_annotated_site_suppresses(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/pipe.py", """\
        import numpy as np

        def _dispatch_step(self):
            # polylint: disable=PL008(cold-start mirror fold, behind a drain)
            return np.asarray(self._dev["tokens"])
    """)
    assert not blocking(findings, "PL008")
    assert any(f.rule == "PL008" and f.suppressed for f in findings)


def test_pl008_scoped_to_engine_package(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/gateway/pipe.py", """\
        import numpy as np

        def _dispatch_step(self):
            return np.asarray(self._dev["tokens"])
    """)
    assert not blocking(findings, "PL008")


# -- suppressions -------------------------------------------------------------


def test_suppression_with_reason_suppresses(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/s.py", """\
        import numpy as np

        def _process_step(self, data):
            # polylint: disable=PL001(deliberate resolve point)
            return np.asarray(data)
    """)
    assert not blocking(findings)
    assert any(f.suppressed and f.reason == "deliberate resolve point"
               for f in findings)


def test_trailing_suppression_on_the_same_line(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/s.py", """\
        import numpy as np

        def _process_step(self, data):
            return np.asarray(data)  # polylint: disable=PL001(resolve point)
    """)
    assert not blocking(findings)


def test_suppression_reason_may_contain_parentheses(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/s.py", """\
        import numpy as np

        def _process_step(self, data):
            # polylint: disable=PL001(async copy (D2H) already landed)
            return np.asarray(data)
    """)
    assert not blocking(findings)
    assert any(f.suppressed and "(D2H)" in f.reason for f in findings)


def test_reasonless_suppression_is_a_finding_and_does_not_suppress(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/s.py", """\
        import numpy as np

        def _process_step(self, data):
            return np.asarray(data)  # polylint: disable=PL001
    """)
    assert blocking(findings, "PL000")
    assert blocking(findings, "PL001")


def test_unused_and_unknown_suppressions_are_findings(tmp_path):
    findings = lint(tmp_path, "polykey_tpu/engine/s.py", """\
        def quiet():
            return 1  # polylint: disable=PL001(nothing fires here)

        def unknown():
            return 2  # polylint: disable=PL999(no such rule)
    """)
    msgs = [f.message for f in blocking(findings, "PL000")]
    assert any("unused suppression" in m for m in msgs)
    assert any("unknown rule" in m for m in msgs)


# -- baseline round-trip ------------------------------------------------------


SILENT = """\
def f(g):
    try:
        g()
    except Exception:
        pass
"""


def test_baseline_round_trip(tmp_path):
    target = tmp_path / "polykey_tpu" / "engine" / "b.py"
    target.parent.mkdir(parents=True)
    target.write_text(SILENT)

    findings = run_paths(tmp_path, ["polykey_tpu"])
    assert blocking(findings)

    baseline_path = tmp_path / "polylint-baseline.json"
    count = write_baseline(baseline_path, findings)
    assert count == len(blocking(findings))

    grandfathered, stale = apply_baseline(
        run_paths(tmp_path, ["polykey_tpu"]), load_baseline(baseline_path)
    )
    assert not blocking(grandfathered)
    assert not stale

    # A NEW violation is not covered by the old baseline...
    target.write_text(SILENT + SILENT.replace("def f", "def h"))
    fresh, _ = apply_baseline(
        run_paths(tmp_path, ["polykey_tpu"]), load_baseline(baseline_path)
    )
    assert len(blocking(fresh)) == 1

    # ...and fixing everything turns the baseline entries stale.
    target.write_text("def f():\n    return 1\n")
    clean, stale = apply_baseline(
        run_paths(tmp_path, ["polykey_tpu"]), load_baseline(baseline_path)
    )
    assert not blocking(clean)
    assert stale


def test_baseline_grandfathers_blocking_twin_of_suppressed_finding(tmp_path):
    # Two findings with identical (rule, path, snippet): one suppressed,
    # one blocking. write_baseline and apply_baseline must agree on
    # occurrence indices or the freshly written baseline fails to cover
    # the blocking one.
    target = tmp_path / "polykey_tpu" / "engine" / "twin.py"
    target.parent.mkdir(parents=True)
    target.write_text(textwrap.dedent("""\
        import numpy as np

        def _process_step(self, data):
            # polylint: disable=PL001(deliberate resolve point)
            a = np.asarray(data)
            a = np.asarray(data)
            return a
    """))
    baseline_path = tmp_path / "polylint-baseline.json"
    first = run_paths(tmp_path, ["polykey_tpu"])
    assert len(blocking(first, "PL001")) == 1
    write_baseline(baseline_path, first)
    grandfathered, stale = apply_baseline(
        run_paths(tmp_path, ["polykey_tpu"]), load_baseline(baseline_path)
    )
    assert not blocking(grandfathered)
    assert not stale


def test_baseline_is_line_number_insensitive(tmp_path):
    target = tmp_path / "polykey_tpu" / "engine" / "b.py"
    target.parent.mkdir(parents=True)
    target.write_text(SILENT)
    baseline_path = tmp_path / "polylint-baseline.json"
    write_baseline(baseline_path, run_paths(tmp_path, ["polykey_tpu"]))

    # Prepend unrelated lines: the finding moves, the fingerprint doesn't.
    target.write_text("import os\n\nUNRELATED = os.sep\n\n\n" + SILENT)
    moved, stale = apply_baseline(
        run_paths(tmp_path, ["polykey_tpu"]), load_baseline(baseline_path)
    )
    assert not blocking(moved)
    assert not stale


# -- CLI ----------------------------------------------------------------------


def test_cli_exit_codes_and_json(tmp_path, capsys):
    pkg = tmp_path / "polykey_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "b.py").write_text(SILENT)

    assert main(["--root", str(tmp_path)]) == 1
    capsys.readouterr()

    assert main(["--root", str(tmp_path), "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["summary"]["blocking"] == 1
    assert payload["findings"][0]["rule"] == "PL003"

    assert main(["--root", str(tmp_path), "--write-baseline"]) == 0
    capsys.readouterr()
    assert main(["--root", str(tmp_path)]) == 0

    assert main(["--root", str(tmp_path / "nope")]) == 2


def test_cli_misspelled_target_is_a_usage_error(tmp_path, capsys):
    # A typo'd target must exit 2, not pass with zero files linted.
    pkg = tmp_path / "polykey_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "b.py").write_text(SILENT)
    assert main(["--root", str(tmp_path), "polykey_tpu/enginee"]) == 2
    assert "enginee" in capsys.readouterr().err


def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("PL001", "PL007"):
        assert rule_id in out


# -- the repo itself ----------------------------------------------------------


def test_self_run_repo_is_clean_under_committed_baseline(capsys):
    """The acceptance gate: `python -m polykey_tpu.analysis` exits 0 on
    this repo with the committed (empty-or-justified) baseline."""
    rc = main(["--root", str(REPO_ROOT)])
    out = capsys.readouterr().out
    assert rc == 0, f"polylint found blocking findings:\n{out}"


def test_default_targets_all_exist():
    """The tiers skip a default target the tree lacks, silently: a
    target that names a deleted file would be linted by nobody."""
    from polykey_tpu.analysis.core import DEFAULT_TARGETS

    assert DEFAULT_TARGETS
    missing = [t for t in DEFAULT_TARGETS if not (REPO_ROOT / t).exists()]
    assert not missing, f"DEFAULT_TARGETS names what is not there: {missing}"


def test_committed_baseline_is_empty_or_justified():
    data = load_baseline(REPO_ROOT / "polylint-baseline.json")
    # Growth contract: debt goes in with an explicit rule/path record,
    # and the file trends toward empty — currently it IS empty.
    assert data["findings"] == {}


@pytest.mark.parametrize("needle", [
    "polylint: disable=PL001(first-token resolve point",
    "polylint: disable=PL001(block resolve point",
    "polylint: disable=PL001(spec-round resolve point",
])
def test_removing_an_engine_suppression_fails_lint(tmp_path, needle):
    """Acceptance: stripping a deliberate-sync annotation out of
    engine.py must make lint fail again."""
    source = (REPO_ROOT / "polykey_tpu" / "engine" / "engine.py").read_text()
    assert needle in source
    stripped = "\n".join(
        line for line in source.splitlines() if needle not in line
    )
    target = tmp_path / "polykey_tpu" / "engine" / "engine.py"
    target.parent.mkdir(parents=True)
    target.write_text(stripped)
    findings = check_file(target, tmp_path)
    assert blocking(findings, "PL001")


def test_baseline_prune_drops_stale_entries(tmp_path, capsys):
    target = tmp_path / "polykey_tpu" / "engine" / "b.py"
    target.parent.mkdir(parents=True)
    target.write_text(SILENT + SILENT.replace("def f", "def h"))
    baseline_path = tmp_path / "polylint-baseline.json"
    write_baseline(baseline_path, run_paths(tmp_path, ["polykey_tpu"]))
    assert len(load_baseline(baseline_path)["findings"]) == 2

    # Fix ONE of the two grandfathered findings: its entry (and only
    # its) must drop; the still-real one survives and keeps gating.
    target.write_text(SILENT)
    rc = main(["--root", str(tmp_path), "--prune"])
    assert rc == 0
    assert "pruned 1 stale" in capsys.readouterr().out
    remaining = load_baseline(baseline_path)["findings"]
    assert len(remaining) == 1
    grandfathered, stale = apply_baseline(
        run_paths(tmp_path, ["polykey_tpu"]), load_baseline(baseline_path)
    )
    assert not blocking(grandfathered)
    assert not stale

    # Nothing stale: prune is a no-op and must not rewrite or create.
    rc = main(["--root", str(tmp_path), "--prune"])
    assert rc == 0
    assert "pruned 0 stale" in capsys.readouterr().out
    assert len(load_baseline(baseline_path)["findings"]) == 1

    # Explicit targets make a partial run: pruning against one would
    # drop live entries for every unscanned file — refused.
    rc = main(["--root", str(tmp_path), "--prune", "polykey_tpu"])
    assert rc == 2
    assert "full run" in capsys.readouterr().err


def test_baseline_prune_without_baseline_file(tmp_path, capsys):
    target = tmp_path / "polykey_tpu" / "engine" / "clean.py"
    target.parent.mkdir(parents=True)
    target.write_text("def f():\n    return 1\n")
    rc = main(["--root", str(tmp_path), "--prune"])
    assert rc == 0
    assert not (tmp_path / "polylint-baseline.json").exists()
