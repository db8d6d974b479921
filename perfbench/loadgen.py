"""Closed-loop load from one process: a thread per client over one channel.

Each client sends its next request when the previous reply has ended (plus
its think time), over the socket, through the streaming RPC a user calls.
Every chunk's arrival instant and character count is recorded on this
process's monotonic clock; with the benchmark's narrowed output head one
character is one token. Nothing here imports JAX.
"""

from __future__ import annotations

import threading
import time

import grpc

from polykey_tpu.proto import polykey_v2_pb2 as pk
from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

# The dev client's channel options (polykey_tpu/gateway/client.py).
CHANNEL_OPTIONS = [
    ("grpc.keepalive_time_ms", 10_000),
    ("grpc.keepalive_timeout_ms", 5_000),
    ("grpc.keepalive_permit_without_calls", 1),
    ("grpc.max_receive_message_length", 4 * 1024 * 1024),
    ("grpc.max_send_message_length", 4 * 1024 * 1024),
]
REQUEST_TIMEOUT_S = 300.0


def generate_request(prompt: str, max_tokens: int, sampling=None):
    """`sampling`: the request parameters of a sampled request
    (traffic.Plan.sampling); None asks for the served API's default,
    greedy."""
    request = pk.ExecuteToolRequest(tool_name="llm_generate")
    request.parameters.update(
        {"prompt": prompt, "max_tokens": max_tokens, **(sampling or {})})
    return request


def new_record(client: int, index: int, prompt_tokens: int, asked: int) -> dict:
    return {"client": client, "index": index, "prompt_tokens": prompt_tokens,
            "asked": asked, "send": None, "times": [], "counts": [],
            "final": None, "status": None, "error": None, "usage": None}


def stream(stub, prompt: str, max_tokens: int, record: dict,
           on_call=None, keep_text: bool = False, sampling=None) -> dict:
    """One streamed generation into `record` (times on time.monotonic);
    the streamed characters are kept only where the caller compares them."""
    if keep_text:
        record["text"] = []
    record["send"] = time.monotonic()
    call = stub.ExecuteToolStream(
        generate_request(prompt, max_tokens, sampling),
        timeout=REQUEST_TIMEOUT_S
    )
    if on_call is not None:
        on_call(call)
    try:
        for chunk in call:
            now = time.monotonic()
            if chunk.final:
                record["final"] = now
                record["status"] = chunk.status.code
                u = chunk.usage
                record["usage"] = {
                    "prompt_tokens": u.prompt_tokens,
                    "completion_tokens": u.completion_tokens,
                    "ttft_ms": u.ttft_ms,
                    "tokens_per_sec": u.tokens_per_sec,
                }
            elif chunk.delta:
                record["times"].append(now)
                record["counts"].append(len(chunk.delta))
                if keep_text:
                    record["text"].append(chunk.delta)
    except grpc.RpcError as e:
        if e.code() == grpc.StatusCode.CANCELLED:
            record["error"] = "cancelled"
        else:
            record["error"] = f"{e.code().name}: {e.details()}"
    return record


class ClosedLoop:
    """All clients of a plan; start(), then stop() when the window ends."""

    def __init__(self, address: str, plan):
        self.plan = plan
        self.channel = grpc.insecure_channel(address, options=CHANNEL_OPTIONS)
        self.stub = PolykeyServiceStub(self.channel)
        self.records: list[dict] = []
        self.late_s: list[float] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._calls: dict[int, object] = {}
        self._threads = [
            threading.Thread(target=self._client, args=(i,), daemon=True,
                             name=f"perfbench-client-{i}")
            for i in range(plan.clients)
        ]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def all_streaming(self) -> bool:
        """Has every client's first request delivered a token?"""
        with self._lock:
            firsts = {r["client"] for r in self.records
                      if r["index"] == 0 and r["times"]}
        return len(firsts) == self.plan.clients

    def _client(self, i: int) -> None:
        k = 0
        while not self._stop.is_set():
            req = self.plan.request(i, k)
            if req.think_s > 0:
                due = time.monotonic() + req.think_s
                if self._stop.wait(req.think_s):
                    return
                # How late this process woke against its own schedule.
                self.late_s.append(time.monotonic() - due)
            record = new_record(i, k, req.prompt_tokens, req.output_tokens)
            with self._lock:
                self.records.append(record)
            stream(self.stub, req.prompt, req.output_tokens, record,
                   on_call=lambda call: self._calls.__setitem__(i, call),
                   sampling=req.sampling)
            if record["error"] and record["error"] != "cancelled":
                # A failing server must not be hammered in a tight loop.
                if self._stop.wait(0.5):
                    return
            k += 1

    def stop(self, first_token_wait_s: float = 30.0) -> None:
        """No new requests; let every request already sent get its first
        token (its TTFT is a sample), then cancel what still streams."""
        self._stop.set()
        deadline = time.monotonic() + first_token_wait_s
        while time.monotonic() < deadline:
            with self._lock:
                waiting = [r for r in self.records
                           if not r["times"] and r["final"] is None
                           and r["error"] is None]
            if not waiting:
                break
            time.sleep(0.02)
        for call in list(self._calls.values()):
            call.cancel()
        for t in self._threads:
            t.join(timeout=30.0)
        self.channel.close()

    def alive(self) -> int:
        return sum(t.is_alive() for t in self._threads)
