"""graphlint (the compiled-graph analysis tier) — a firing AND a
non-firing fixture for every GL check, plus the suppression/baseline
machinery and an engine-backed integration tier.

Unit fixtures exercise the check cores directly (synthetic jits and
jaxprs — fast); the integration tests run the real checks against a
smoke-profile CPU engine, and the full-profile self-run (what `make
graphlint` gates on) is marked slow.
"""

import contextlib
import json
import warnings

import jax
import jax.numpy as jnp
import pytest

from polykey_tpu.analysis import graph
from polykey_tpu.analysis.baseline import apply_baseline, write_baseline
from polykey_tpu.analysis.graph import (
    GraphEnv,
    abstract_contract,
    apply_check_suppressions,
    audit_donation_site,
    callback_findings,
    dtype_findings,
    gate_consistency_findings,
    graph_finding,
    recompile_findings,
    sharding_divisibility,
)


# -- GL001: recompile stability ----------------------------------------------


def _jit_square():
    return jax.jit(lambda x: x * x)


def test_gl001_fires_on_shape_unstable_jit():
    handle = _jit_square()
    handle(jnp.ones((4,)))  # "warmup"

    def drive():
        # A deliberately shape-unstable serving sweep: every new shape is
        # a new executable.
        handle(jnp.ones((8,)))
        handle(jnp.ones((16,)))
        return []

    findings, sizes = recompile_findings("fixture", {"square": handle}, drive)
    grew = [f for f in findings if f.rule == "GL001"
            and f.snippet.endswith(":grew")]
    assert len(grew) == 1
    assert "2 new executable" in grew[0].message
    assert sizes["square"] == (1, 3)


def test_gl001_clean_on_shape_stable_jit():
    handle = _jit_square()
    handle(jnp.ones((4,)))

    def drive():
        for _ in range(3):
            handle(jnp.ones((4,)))
        return []

    findings, sizes = recompile_findings("fixture", {"square": handle}, drive)
    assert findings == []
    assert sizes["square"] == (1, 1)


def test_gl001_fires_on_warmup_gap():
    handle = _jit_square()  # never warmed
    findings, _ = recompile_findings(
        "fixture", {"square": handle}, lambda: [])
    assert any(f.snippet.endswith(":cold") for f in findings)


def test_gl001_surfaces_drive_errors_as_gl000():
    handle = _jit_square()
    handle(jnp.ones((4,)))
    findings, _ = recompile_findings(
        "fixture", {"square": handle}, lambda: ["engine wedged"])
    assert any(f.rule == "GL000" and "engine wedged" in f.message
               for f in findings)


# -- GL002: donation audit ----------------------------------------------------


def test_gl002_fires_when_donation_dropped():
    # The donated arg's dtype matches no output → XLA cannot alias it and
    # warns; the audit must fail on that warning.
    fn = jax.jit(
        lambda x, y: (x + y).astype(jnp.bfloat16), donate_argnames=("x",))
    args = (jnp.ones((64, 64)), jnp.ones((64, 64)))
    findings = audit_donation_site(
        "fixture.dropped", lambda: fn.lower(*args), donated_big_leaves=1)
    assert any(f.rule == "GL002" and "dropped" in f.snippet
               for f in findings)


def test_gl002_fires_on_alias_deficit():
    # No donation at all (the "removed donate_argnames" regression): the
    # compiled executable aliases nothing, so auditing it against one
    # expected donated buffer must fail.
    fn = jax.jit(lambda x, y: x + y)
    args = (jnp.ones((64, 64)), jnp.ones((64, 64)))
    findings = audit_donation_site(
        "fixture.nodonate", lambda: fn.lower(*args), donated_big_leaves=1)
    assert any(f.rule == "GL002" and "alias-deficit" in f.snippet
               for f in findings)


def test_gl002_clean_on_aliased_donation():
    fn = jax.jit(lambda x, y: x + y, donate_argnames=("x",))
    args = (jnp.ones((64, 64)), jnp.ones((64, 64)))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a dropped donation would raise
        findings = audit_donation_site(
            "fixture.good", lambda: fn.lower(*args), donated_big_leaves=1)
    assert findings == []


def test_gl002_lower_failure_is_blocking_gl000():
    def broken_lower():
        raise RuntimeError("no such handle")

    findings = audit_donation_site("fixture.broken", broken_lower, 1)
    assert any(f.rule == "GL000" for f in findings)


# -- GL003: dtype policy ------------------------------------------------------

_W_SHAPE = (32, 64)


def test_gl003_fires_on_weight_upcast_in_bf16_path():
    def fn(w, x):
        return x @ w.astype(jnp.float32)  # the classic silent upcast

    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros(_W_SHAPE, jnp.bfloat16), jnp.zeros((4, 32), jnp.float32))
    findings = dtype_findings("fixture", jaxpr, {_W_SHAPE}, bf16_path=True)
    assert any(f.rule == "GL003" and "upcast" in f.snippet
               for f in findings)


def test_gl003_activation_upcast_does_not_fire():
    # Mixed-precision activations (norm/softmax in f32) are the design;
    # only weight-shaped operands may fire.
    def fn(w, x):
        h = (x.astype(jnp.float32) ** 2).astype(jnp.bfloat16)
        return h @ w

    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros(_W_SHAPE, jnp.bfloat16), jnp.zeros((4, 32), jnp.bfloat16))
    assert dtype_findings("fixture", jaxpr, {_W_SHAPE}, bf16_path=True) == []


def test_gl003_fires_on_f64_anywhere():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.zeros((8,)))
    findings = dtype_findings("fixture", jaxpr, set(), bf16_path=False)
    assert any(f.rule == "GL003" and ":f64:" in f.snippet
               for f in findings)


def test_gl003_walks_nested_jaxprs():
    # The f64 hides inside a scan body — the walk must descend.
    with jax.enable_x64(True):
        def fn(x):
            def body(carry, _):
                return carry + x.astype(jnp.float64).sum(), None
            out, _ = jax.lax.scan(body, 0.0, None, length=3)
            return out

        jaxpr = jax.make_jaxpr(fn)(jnp.zeros((8,)))
    findings = dtype_findings("fixture", jaxpr, set(), bf16_path=False)
    assert any(":f64:" in f.snippet for f in findings)


# -- GL004: host-transfer guard -----------------------------------------------


def test_gl004_fires_on_debug_callback_in_step():
    def fn(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    jaxpr = jax.make_jaxpr(fn)(jnp.ones((4,)))
    findings = callback_findings("fixture", jaxpr)
    assert any(f.rule == "GL004" and "callback" in f.message
               for f in findings)


def test_gl004_clean_on_pure_step():
    jaxpr = jax.make_jaxpr(lambda x: x * 2 + 1)(jnp.ones((4,)))
    assert callback_findings("fixture", jaxpr) == []


# -- GL005: shape/layout contracts --------------------------------------------


def _mesh_tp2():
    from polykey_tpu.parallel.mesh import MeshConfig, create_mesh

    return create_mesh(MeshConfig(tp=2), jax.devices()[:2])


def test_gl005_fires_on_indivisible_sharded_dim():
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(_mesh_tp2(), PartitionSpec(None, "tp"))
    findings = sharding_divisibility("fixture", (4, 3), sharding)
    assert len(findings) == 1 and findings[0].rule == "GL005"
    assert "3 % 2" in findings[0].message


def test_gl005_clean_on_divisible_sharded_dim():
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(_mesh_tp2(), PartitionSpec(None, "tp"))
    assert sharding_divisibility("fixture", (4, 6), sharding) == []


def test_gl005_abstract_contract_fires_on_mismatch():
    findings = abstract_contract(
        "fixture", lambda x: x[:2], (jnp.zeros((4, 4)),),
        [((4, 4), "float32")])
    assert any("out-contract" in f.snippet for f in findings)


def test_gl005_abstract_contract_fires_on_trace_error():
    def broken(x):
        raise ValueError("block shape does not divide grid")

    findings = abstract_contract(
        "fixture", broken, (jnp.zeros((4,)),), [((4,), "float32")])
    assert any("abstract-eval" in f.snippet for f in findings)


def test_gl005_abstract_contract_clean():
    assert abstract_contract(
        "fixture", lambda x: x * 2, (jnp.zeros((4, 4)),),
        [((4, 4), "float32")]) == []


def test_gl005_gate_consistency_firing_and_clean():
    from dataclasses import replace

    from polykey_tpu.models.config import TINY_LLAMA

    # folded lanes 32*4=128 → gate-eligible, but head_dim 4 mis-tiles.
    bad = replace(TINY_LLAMA, name="bad-geom", num_kv_heads=32, head_dim=4)
    findings = gate_consistency_findings([bad])
    assert any("paged-gate:bad-geom" == f.snippet for f in findings)
    assert gate_consistency_findings([TINY_LLAMA]) == []


# -- suppressions + baseline --------------------------------------------------


def test_check_suppression_marks_finding(monkeypatch):
    finding = graph_finding("GL003", "graph:x", "x:upcast:(1, 2)", "msg")
    check = graph._GRAPH_REGISTRY["GL003"]
    monkeypatch.setattr(
        check, "SUPPRESSIONS",
        {"x:upcast:(1, 2)": "reviewed: deliberate f32 residual"})
    out = apply_check_suppressions([finding])
    assert out[0].suppressed and "reviewed" in out[0].reason
    assert not out[0].blocking


def test_unsuppressed_finding_stays_blocking():
    finding = graph_finding("GL001", "graph:x", "x:key", "msg")
    out = apply_check_suppressions([finding])
    assert not out[0].suppressed and out[0].blocking


def test_graph_findings_roundtrip_the_baseline(tmp_path):
    findings = [
        graph_finding("GL001", "graph:engine.plain", "k1", "grew"),
        graph_finding("GL002", "graph:train", "k2", "dropped"),
    ]
    path = tmp_path / "graphlint-baseline.json"
    assert write_baseline(path, findings) == 2
    from polykey_tpu.analysis.baseline import load_baseline

    marked, stale = apply_baseline(findings, load_baseline(path))
    assert all(f.baselined for f in marked) and stale == []
    # A fixed finding's entry goes stale (prune signal).
    marked, stale = apply_baseline(findings[:1], load_baseline(path))
    assert len(stale) == 1


def test_cli_list_checks(capsys):
    assert graph.main(["--list-checks"]) == 0
    out = capsys.readouterr().out
    for check_id in ("GL001", "GL002", "GL003", "GL004", "GL005"):
        assert check_id in out


def test_cli_only_rejects_unknown_check_id(capsys):
    # A typo'd id silently running zero checks would read as a clean
    # graph; the CLI must refuse instead.
    assert graph.main(["--only", "GL01,GL004"]) == 2
    err = capsys.readouterr().err
    assert "unknown check id" in err and "GL01" in err


def test_cli_prune_requires_full_run(capsys):
    assert graph.main(["--only", "GL003", "--prune"]) == 2
    assert "full run" in capsys.readouterr().err


def test_cli_write_baseline_requires_full_run(capsys):
    # Rewriting the baseline from a partial run would silently discard
    # every other check's grandfathered entries.
    assert graph.main(["--only", "GL003", "--write-baseline"]) == 2
    assert "full run" in capsys.readouterr().err


def test_cli_write_baseline_refuses_gl000(tmp_path, monkeypatch, capsys):
    # GL000 = the analyzer itself is broken (a partial run in disguise);
    # grandfathering from it would drop the crashed check's live entries
    # and make graphlint exit 0 forever. The file must stay untouched.
    path = tmp_path / "graphlint-baseline.json"
    write_baseline(
        path, [graph_finding("GL001", "graph:engine.plain", "k1", "grew")])
    findings = [
        graph_finding("GL000", "graph:GL001", "GL001:crashed", "probe gone"),
        graph_finding("GL005", "graph:flash", "k5", "bad block"),
    ]
    monkeypatch.setattr(
        graph, "run_graph_checks",
        lambda env, only=None: (findings, env))
    assert graph.main(["--root", str(tmp_path), "--write-baseline"]) == 1
    assert "refusing to write" in capsys.readouterr().err
    from polykey_tpu.analysis.baseline import load_baseline

    entries = load_baseline(path)["findings"]
    assert len(entries) == 1  # pre-existing GL001 entry untouched
    assert all(e["rule"] == "GL001" for e in entries.values()), entries


def test_cli_prune_refuses_on_gl000(tmp_path, monkeypatch, capsys):
    # A crashed check replaced its real findings with GL000; pruning
    # against that run would drop the crashed check's live entries.
    findings = [
        graph_finding("GL000", "graph:GL001", "GL001:crashed", "probe gone"),
    ]
    path = tmp_path / "graphlint-baseline.json"
    write_baseline(
        path, [graph_finding("GL001", "graph:engine.plain", "k1", "grew")])
    monkeypatch.setattr(
        graph, "run_graph_checks",
        lambda env, only=None: (findings, env))
    assert graph.main(["--root", str(tmp_path), "--prune"]) == 1
    assert "refusing to prune" in capsys.readouterr().err
    from polykey_tpu.analysis.baseline import load_baseline

    assert len(load_baseline(path)["findings"]) == 1  # untouched


def test_cli_only_does_not_report_unrun_checks_stale(
        tmp_path, monkeypatch, capsys):
    # Baseline holds GL001 debt; an --only GL003 run must not claim the
    # GL001 entry is a fixed finding (false debt-paid signal).
    path = tmp_path / "graphlint-baseline.json"
    write_baseline(
        path, [graph_finding("GL001", "graph:engine.plain", "k1", "grew")])
    monkeypatch.setattr(
        graph, "run_graph_checks", lambda env, only=None: ([], env))
    assert graph.main(
        ["--root", str(tmp_path), "--only", "GL003", "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)["summary"]
    assert summary["stale_baseline_entries"] == []


def test_cli_prune_drops_stale_graph_entries(tmp_path, monkeypatch, capsys):
    # Baseline two findings, then monkeypatch the run to produce only one:
    # --prune must drop exactly the stale entry and keep the live one.
    findings = [
        graph_finding("GL001", "graph:engine.plain", "k1", "grew"),
        graph_finding("GL002", "graph:train", "k2", "dropped"),
    ]
    path = tmp_path / "graphlint-baseline.json"
    assert write_baseline(path, findings) == 2
    monkeypatch.setattr(
        graph, "run_graph_checks",
        lambda env, only=None: (findings[:1], env))
    assert graph.main(["--root", str(tmp_path), "--prune"]) == 0
    assert "pruned 1 stale" in capsys.readouterr().out
    from polykey_tpu.analysis.baseline import load_baseline

    assert len(load_baseline(path).get("findings", {})) == 1


# -- integration: the real checks against a smoke-profile engine --------------


@pytest.fixture(scope="module")
def smoke_env():
    env = GraphEnv(profile="smoke")
    yield env
    env.close()


def test_gl001_real_engine_is_compile_stable(smoke_env):
    check = graph._GRAPH_REGISTRY["GL001"]
    findings = check.run(smoke_env)
    assert findings == [], [f.render() for f in findings]


def test_gl002_real_donation_sites_are_aliased(smoke_env):
    check = graph._GRAPH_REGISTRY["GL002"]
    findings = check.run(smoke_env)
    assert findings == [], [f.render() for f in findings]


def test_gl004_guard_smoke_clean_and_guard_restored(smoke_env):
    # Preset a per-direction guard: the smoke's save/restore must not
    # wipe it (restoring only the umbrella would, since the umbrella
    # propagates into the per-direction options on update).
    prev = jax.config.jax_transfer_guard_device_to_device
    jax.config.update("jax_transfer_guard_device_to_device", "log")
    try:
        check = graph._GRAPH_REGISTRY["GL004"]
        findings = check._guarded_smoke(smoke_env)
        assert findings == [], [f.render() for f in findings]
        # The guard must be restored — later tests upload numpy freely.
        assert jax.config.jax_transfer_guard in (None, "allow")
        assert jax.config.jax_transfer_guard_device_to_device == "log"
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", prev)


def test_host_crossing_honors_per_direction_guard():
    """The nullcontext fast path must NOT engage when a per-direction
    guard option is set (the umbrella propagates into the directions on
    update, but a per-direction update never reflects back)."""
    from polykey_tpu.engine import engine as engine_mod

    assert isinstance(engine_mod._host_crossing(), contextlib.nullcontext)
    prev = jax.config.jax_transfer_guard_device_to_host
    jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    try:
        assert not isinstance(
            engine_mod._host_crossing(), contextlib.nullcontext)
    finally:
        jax.config.update("jax_transfer_guard_device_to_host", prev)


def test_gl004_trips_without_host_crossing_annotations():
    """Removing the engine's _host_crossing annotations must trip the
    guarded smoke — proves the guard has teeth end-to-end (a sacrificial
    engine: the tripped merges poison its slots)."""
    from polykey_tpu.engine import engine as engine_mod

    def _no_annotation(site: str = "unlabeled"):
        return contextlib.nullcontext()

    original = engine_mod._host_crossing
    engine_mod._host_crossing = _no_annotation
    env = GraphEnv(profile="smoke")
    try:
        check = graph._GRAPH_REGISTRY["GL004"]
        findings = check._guarded_smoke(env)
        assert any(f.rule == "GL004" for f in findings)
    finally:
        engine_mod._host_crossing = original
        env.close()


@pytest.mark.slow
def test_full_graphlint_self_run_clean():
    """The `make graphlint` gate: every check, full profile, zero
    blocking findings on this repo."""
    findings, env = graph.run_graph_checks()
    try:
        blocking = [f for f in findings if f.blocking]
        assert blocking == [], [f.render() for f in blocking]
    finally:
        env.close()
