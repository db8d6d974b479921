"""What the process runs on (engine/device.py) and where engines land:
the platform gate at the serving entry point, replica i on device slice
i, the disagg refusal on a TPU host, the compile census."""

import dataclasses

import jax
import pytest

from polykey_tpu.engine import device
from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import InferenceEngine

TINY = EngineConfig(
    model="tiny-llama", dtype="float32", max_decode_slots=2, page_size=8,
    num_pages=16, max_seq_len=32, prefill_buckets=(16,),
    supervise=False, signals_interval_s=0, timeline_capacity=0,
)


def test_backend_tpu_refuses_a_silent_cpu_fallback(monkeypatch):
    """POLYKEY_BACKEND=tpu on a non-TPU platform is a start-up error
    unless JAX_PLATFORMS=cpu was set explicitly — before any engine is
    built."""
    from polykey_tpu.gateway.tpu_service import TpuService

    monkeypatch.setenv("POLYKEY_COMPILE_CACHE", "0")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="no TPU is visible"):
        device.require_accelerator()
    built = []
    monkeypatch.setattr(
        "polykey_tpu.gateway.tpu_service.InferenceEngine",
        lambda *a, **k: built.append(1),
    )
    with pytest.raises(RuntimeError, match="POLYKEY_BACKEND=tpu"):
        TpuService.from_env()
    assert built == []

    monkeypatch.setenv("JAX_PLATFORMS", " CPU ")
    identity = device.require_accelerator()
    assert identity["platform"] == "cpu" and identity["chip"] is None
    assert identity["device_count"] == len(jax.devices())


def test_server_exits_1_when_the_backend_is_refused(monkeypatch):
    from polykey_tpu.gateway import server

    monkeypatch.setenv("POLYKEY_BACKEND", "tpu")
    monkeypatch.setenv("POLYKEY_COMPILE_CACHE", "0")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exit_info:
        server.serve(address="127.0.0.1:0")
    assert exit_info.value.code == 1


def _devices_of(config) -> list:
    engine = InferenceEngine(config)
    try:
        ids = [d.id for d in engine.mesh.devices.flat]
        # The params really live there, not just the mesh object.
        assert {d.id for d in engine.params["embed"].devices()} == set(ids)
        assert {d.id for d in engine.paged.kv.devices()} == set(ids)
        assert engine.stats()["devices"] == ids
        return ids
    finally:
        engine.shutdown()


def test_replica_i_takes_device_slice_i():
    """8 simulated devices: replicas x devices-per-engine slices that fit
    are disjoint and in order; when they do not fit, every replica shares
    the first slice (DEPLOY.md placement rule) and says so in the log."""
    def replica(i, n, **kw):
        return dataclasses.replace(TINY, replica=i, replicas=n, **kw)

    assert _devices_of(replica(3, 4)) == [3]
    assert _devices_of(replica(1, 2, tp=2)) == [2, 3]
    # Not in a pool: `replica` is only an identity (a disagg worker's
    # index within its tier, engine/worker.py) and the engine takes the
    # first slice — on a one-device host slice 1 would be empty.
    assert _devices_of(replica(1, 1)) == [0]
    with pytest.raises(ValueError, match="not one of replicas=2"):
        InferenceEngine(replica(2, 2))

    class Log:
        warned = []

        def warn(self, msg, **fields):
            self.warned.append((msg, fields))

        def info(self, msg, **fields):      # `engine started`
            pass

    engine = InferenceEngine(replica(1, 5, tp=2), logger=Log())
    try:
        assert [d.id for d in engine.mesh.devices.flat] == [0, 1]
    finally:
        engine.shutdown()
    assert Log.warned and Log.warned[0][0] == "replicas share devices"


def test_replica_pool_passes_the_pool_size_down():
    from polykey_tpu.engine.replica_pool import ReplicaPool

    pool = ReplicaPool.create(TINY, replicas=2)
    try:
        devices = [r["devices"] for r in pool.stats()["per_replica"]]
    finally:
        pool.shutdown()
    assert devices == [[0], [1]]


@pytest.mark.parametrize("option, message", [
    (dict(kv_dtype="int8"), "POLYKEY_KV_DTYPE.*aligned to tiling"),
])
def test_options_whose_kernels_fail_on_tpu_are_refused_at_start(
    monkeypatch, option, message,
):
    """An option whose Pallas path does not compile (or is not valid) on
    the chip stops the engine at start on TPU with the compiler's message
    — it never serves from a slower path under the option's name. On the
    CPU the same configs build (every other test uses them)."""
    monkeypatch.setattr(
        "polykey_tpu.engine.engine.device_identity", lambda: {
            "platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 1, "chip": "tpu-v5e",
        })
    with pytest.raises(ValueError, match=message):
        InferenceEngine(dataclasses.replace(TINY, **option))


def test_disagg_spawn_is_refused_on_a_tpu_host(monkeypatch):
    """A TPU belongs to one process; P+D worker processes cannot share it.
    The pool must say so at once instead of waiting out the readiness
    timeout with the workers' stderr discarded."""
    import time

    from polykey_tpu.engine.disagg_pool import DisaggPool

    monkeypatch.setattr(device, "device_identity", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite",
        "device_count": 1, "chip": "tpu-v5e",
    })
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="one process at a time"):
        DisaggPool.create(dataclasses.replace(TINY, disagg="1x1"))
    assert time.monotonic() - t0 < 5.0


def test_compile_census_counts_executables():
    import jax.numpy as jnp

    device.install_compile_census()
    device.install_compile_census()          # idempotent
    x = jnp.arange(611.0)                    # builds its own executable
    before = device.compile_counts()
    jax.jit(lambda x: x * 5 + 2)(x).block_until_ready()
    after = device.compile_counts()
    assert after["executables"] == before["executables"] + 1
    assert after["fresh_compiles"] == (
        after["executables"] - after["cache_hits"])


def test_inspecting_a_step_builds_it_once():
    """engine._warm_call lowers and (on a mesh) compiles the first prefill
    and decode step to read their kernels and collectives, then dispatches
    the jitted function. That must cost ONE backend compile: the AOT path
    and the dispatch share the jit's lowering cache, so what was inspected
    is what serves. A JAX that stops sharing would double the two largest
    compiles of every multi-chip start — this pins it."""
    from functools import partial

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    w = jax.device_put(jnp.ones((64, 96)), NamedSharding(mesh, P("tp", None)))
    x = jax.device_put(jnp.ones((8, 64)), NamedSharding(mesh, P(None, "tp")))

    @partial(jax.jit, static_argnames=("scale",), donate_argnums=(0,))
    def step(x, w, scale):
        return jnp.tanh(x @ w) * scale

    device.install_compile_census()
    before = device.compile_counts()["executables"]
    lowered = step.lower(x, w, scale=3)
    assert device.mosaic_calls(lowered) == {}
    assert device.collective_ops(lowered.compile()) >= 1
    step(x, w, scale=3).block_until_ready()
    assert device.compile_counts()["executables"] == before + 1


def test_mosaic_calls_names_the_kernels():
    """A step lowered for TPU names each Pallas kernel it carries (the
    pallas_call's `name=`), counted per call site — a scan body once."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def call(x):
        return pl.pallas_call(
            double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            name="double_rows",
        )(x)

    def step(x):
        def layer(carry, _):
            return call(carry) + call(carry), None
        return jax.lax.scan(layer, x, None, length=3)[0]

    x = jnp.ones((8, 128), jnp.float32)
    lowered = jax.jit(step).trace(x).lower(lowering_platforms=("tpu",))
    assert device.mosaic_calls(lowered) == {"double_rows": 2}
