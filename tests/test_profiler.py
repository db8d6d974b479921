"""jax.profiler trace endpoint (VERDICT r1 #7, SURVEY §5 tracing).

engine_profile start → serve a request (annotated prefill/decode steps) →
stop must leave a real trace artifact on disk.
"""

import glob
import os

import pytest
from google.protobuf import struct_pb2

from polykey_tpu.engine.config import EngineConfig
from polykey_tpu.engine.engine import InferenceEngine
from polykey_tpu.gateway.tpu_service import TpuService

CONFIG = EngineConfig(
    model="tiny-llama",
    tokenizer="byte",
    dtype="float32",
    max_decode_slots=2,
    page_size=8,
    num_pages=32,
    max_seq_len=64,
    prefill_buckets=(16, 32),
    max_new_tokens_cap=16,
)


def _params(**kv) -> struct_pb2.Struct:
    s = struct_pb2.Struct()
    s.update(kv)
    return s


def test_profile_capture_roundtrip(tmp_path):
    engine = InferenceEngine(CONFIG)
    service = TpuService(engine)
    try:
        log_dir = str(tmp_path / "trace")
        resp = service.execute_tool(
            "engine_profile", _params(action="start", log_dir=log_dir),
            None, None,
        )
        assert resp.struct_output["profiling"] is True

        # Double-start is an error.
        with pytest.raises(ValueError):
            service.execute_tool(
                "engine_profile", _params(action="start"), None, None
            )

        # Generate under the trace so prefill/decode annotations land.
        resp = service.execute_tool(
            "llm_generate", _params(prompt="profile me", max_tokens=4),
            None, None,
        )
        assert resp.status.code == 200

        resp = service.execute_tool(
            "engine_profile", _params(action="stop"), None, None
        )
        assert resp.struct_output["profiling"] is False

        traces = glob.glob(
            os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
        )
        assert traces, f"no trace artifact under {log_dir}"

        # Stop without start is an error; status is not.
        with pytest.raises(ValueError):
            service.execute_tool(
                "engine_profile", _params(action="stop"), None, None
            )
        resp = service.execute_tool(
            "engine_profile", _params(action="status"), None, None
        )
        assert resp.struct_output["profiling"] is False
    finally:
        engine.shutdown()


def test_capture_holds_the_program_spans_and_no_python_frames(tmp_path):
    """A capture is started without JAX's Python tracer (ISSUE 53: its
    hook froze the streams a capture measures): the `polykey/` spans the
    benchmark reads are in it, and no event of the tracer's (`$file:line
    function` frames) is."""
    from jax.profiler import ProfileData

    engine = InferenceEngine(CONFIG)
    service = TpuService(engine)
    try:
        log_dir = str(tmp_path / "trace")
        service.execute_tool(
            "engine_profile", _params(action="start", log_dir=log_dir),
            None, None,
        )
        resp = service.execute_tool(
            "llm_generate", _params(prompt="profile me", max_tokens=4),
            None, None,
        )
        assert resp.status.code == 200
        service.execute_tool(
            "engine_profile", _params(action="stop"), None, None
        )
    finally:
        engine.shutdown()
    (trace,) = glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True
    )
    names = {
        event.name
        for plane in ProfileData.from_file(trace).planes
        for line in plane.lines
        for event in line.events
    }
    assert {"polykey/decode", "polykey/prefill"} <= names
    assert not [name for name in names if name.startswith("$")]
