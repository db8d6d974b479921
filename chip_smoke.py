"""Proof on demand that the serving path starts and answers on the chip.

    python3 chip_smoke.py                   # one v5e chip: llama-3-8b, int8 weights
    python3 chip_smoke.py --tp 4 --quantize none    # four chips, bf16, one engine
    python3 chip_smoke.py --replicas 4      # four one-chip replicas, one process
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny  # CPU rehearsal, tiny-llama

Drives the main path once through the entry points a user calls: starts
`python -m polykey_tpu.gateway.server` with POLYKEY_BACKEND=tpu as a child
(this process never imports JAX — a chip belongs to one process), waits
for gRPC health SERVING, sends llm_generate requests over the socket with
polykey_tpu.gateway.client (one unary, one streamed, then a concurrent
handful with >=128-token prompts so the flash prefill bucket, batched
admission, the K-step decode block and the KV write kernel all run),
reads engine_stats, sends SIGTERM and expects a clean stop. Full width and
depth of the model, registry-default engine geometry, random weights from
the engine's seed, prompts from a seed here.

It fails unless the platform is a TPU from the roofline table, every
request returned the tokens it asked for with Usage filled, no engine
restarted, no request failed, decode blocks were dispatched, and the
warmed prefill and decode executables carry the Mosaic custom calls of the
flash, paged-decode and KV-write kernels, by name. The
last stdout line is then {"ok": true, "device": {...}}; on any failure
the exit code is non-zero and no such line is printed. It prints counts,
sizes and start-up seconds — never a rate. `--tiny` runs the same code
on the CPU paths the tests use and says platform=cpu.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")

START_TIMEOUT_S = 900.0      # init + every warm-up compile, cold
REQUEST_TIMEOUT_S = 300.0
STOP_TIMEOUT_S = 60.0
LONG_PROMPT_CHARS = 160      # byte tokenizer: > the 128-token bucket
CONCURRENT = 6
REQUESTS = 2 + CONCURRENT  # one unary, one streamed, the concurrent handful
# The Pallas kernels on the default path, by the name their pallas_call
# gives the Mosaic custom call; engine_stats lists the names found in the
# lowered text of the warmed prefill and decode steps.
SERVED_KERNELS = {
    "prefill": ("flash_attention",),
    "decode": ("paged_attention_decode", "paged_kv_write"),
}


class SmokeFailure(Exception):
    """One contract clause did not hold; the message names it."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def server_env(args, port: int) -> dict:
    env = dict(os.environ)
    env.update({
        "POLYKEY_BACKEND": "tpu",
        "POLYKEY_MODEL": "tiny-llama" if args.tiny else args.model,
        "POLYKEY_COMPILE_WARMUP": "1",
        "POLYKEY_TP": str(args.tp),
        "POLYKEY_REPLICAS": str(args.replicas),
        "POLYKEY_METRICS_PORT": "0",
        "LISTEN_ADDR": f"127.0.0.1:{port}",
        "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    })
    if args.tiny:
        env["JAX_PLATFORMS"] = "cpu"
        env["POLYKEY_DTYPE"] = "float32"
    elif args.quantize != "none":
        env["POLYKEY_QUANTIZE"] = args.quantize
    return env


def wait_serving(proc: subprocess.Popen, address: str) -> None:
    import grpc

    from polykey_tpu.proto import health_v1_pb2 as health_pb
    from polykey_tpu.proto.health_v1_grpc import HealthStub

    deadline = time.monotonic() + START_TIMEOUT_S
    with grpc.insecure_channel(address) as channel:
        stub = HealthStub(channel)
        while time.monotonic() < deadline:
            check(proc.poll() is None,
                  f"server child exited with code {proc.returncode} "
                  "before serving")
            try:
                reply = stub.Check(
                    health_pb.HealthCheckRequest(service=""), timeout=2.0
                )
                if reply.status == health_pb.HealthCheckResponse.SERVING:
                    return
            except grpc.RpcError:
                pass            # not listening yet: the engine is starting
            time.sleep(1.0)
    raise SmokeFailure(f"server not SERVING within {START_TIMEOUT_S:.0f}s")


def engine_stats(client) -> dict:
    from google.protobuf.json_format import MessageToDict

    from polykey_tpu.proto import polykey_v2_pb2 as pk

    reply = client.execute_tool(
        pk.ExecuteToolRequest(tool_name="engine_stats"), timeout=60.0
    )
    check(reply.status.code == 200, f"engine_stats status {reply.status}")
    return MessageToDict(reply.struct_output)


def engines_of(stats: dict) -> list:
    """Per-engine stats: a replica pool nests them under per_replica."""
    return stats.get("per_replica") or [stats]


def generate_request(prompt: str, max_tokens: int):
    from polykey_tpu.proto import polykey_v2_pb2 as pk

    request = pk.ExecuteToolRequest(tool_name="llm_generate")
    request.parameters.update({"prompt": prompt, "max_tokens": max_tokens})
    return request


def stream_one(client, prompt: str, max_tokens: int) -> None:
    """One server-streamed generation; checks its terminal chunk."""
    final = None
    for chunk in client.stub.ExecuteToolStream(
        generate_request(prompt, max_tokens), timeout=REQUEST_TIMEOUT_S
    ):
        if chunk.final:
            final = chunk
    check(final is not None, "stream ended without a final chunk")
    check(final.status.code == 200, f"stream status {final.status}")
    usage = final.usage
    check(usage.completion_tokens == max_tokens,
          f"asked {max_tokens} tokens, Usage says {usage.completion_tokens}")
    # Byte tokenizer: one token per UTF-8 byte plus BOS.
    check(usage.prompt_tokens == len(prompt.encode()) + 1,
          f"Usage.prompt_tokens {usage.prompt_tokens} for a "
          f"{len(prompt.encode())}-byte prompt")
    check(usage.ttft_ms > 0 and usage.tokens_per_sec > 0,
          "Usage timing fields not filled")


def drive(client, rng: random.Random) -> int:
    """Send the smoke's traffic; returns the tokens asked for in total."""
    def prompt(chars: int) -> str:
        return "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ")
                       for _ in range(chars))

    asked = 0
    reply = client.execute_tool(
        generate_request(prompt(40), 16), timeout=REQUEST_TIMEOUT_S
    )
    check(reply.status.code == 200, f"unary status {reply.status}")
    check(reply.WhichOneof("output") == "string_output",
          "unary reply carries no string_output")
    asked += 16
    stream_one(client, prompt(40), 32)
    asked += 32

    errors: list = []
    prompts = [prompt(LONG_PROMPT_CHARS) for _ in range(CONCURRENT)]

    def worker(text: str) -> None:
        try:
            stream_one(client, text, 24)
        except Exception as e:      # reported below, on the main thread
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(p,)) for p in prompts]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(REQUEST_TIMEOUT_S + 30)
        check(not thread.is_alive(), "a concurrent request never returned")
    check(not errors, f"concurrent requests failed: {errors[:1]}")
    return asked + 24 * CONCURRENT


def total(engines: list, key: str) -> int:
    return sum(int(e.get(key, 0)) for e in engines)


def check_stats(args, last: dict, asked: int) -> dict:
    """The contract's engine-side clauses; returns the device identity."""
    engines = engines_of(last)
    head = engines[0]
    device = {"platform": head["platform"],
              "kind": head["device_kind"],
              "count": int(head["device_count"])}
    if args.tiny:
        check(device["platform"] == "cpu", f"--tiny ran on {device}")
    else:
        check(device["platform"] == "tpu",
              f"platform is {device['platform']!r}, not tpu")
        check(head.get("chip"),
              f"device_kind {device['kind']!r} is not in the roofline table")
        for eng in engines:
            calls = eng["warmup_mosaic_calls"]
            for step, kernels in SERVED_KERNELS.items():
                missing = set(kernels) - set(calls.get(step, {}))
                check(not missing,
                      f"the served {step} executable lacks the Mosaic "
                      f"kernel(s) {sorted(missing)}: {calls}")
    # The unary reply has no Usage, so its length is checked here: decode
    # blocks emit every token but a request's first, which prefill samples.
    completed = total(engines, "requests_completed")
    check(completed == REQUESTS, f"{completed} of {REQUESTS} requests completed")
    emitted = total(engines, "tokens_generated")
    check(emitted == asked - REQUESTS,
          f"asked {asked} tokens over {REQUESTS} requests, decode emitted "
          f"{emitted} (expected {asked - REQUESTS})")
    failed = total(engines, "requests_failed")
    check(failed == 0, f"{failed} request(s) failed")
    restarts = int(last.get("engine_restarts", 0))
    check(restarts == 0, f"{restarts} engine restart(s)")
    check(total(engines, "blocks_dispatched") > 0,
          "no decode block was dispatched")
    if device["count"] >= args.replicas * args.tp:
        slices = {tuple(e["devices"]) for e in engines}
        check(len(slices) == len(engines),
              "replicas share devices although the host has a slice each")
    return device


def report(args, device: dict, first: dict, last: dict, asked: int) -> None:
    """Counts, sizes and start-up evidence — never a rate."""
    from importlib.metadata import PackageNotFoundError, version

    def installed(package: str) -> str:
        try:
            return version(package)
        except PackageNotFoundError:
            return "absent"

    warm, engines = engines_of(first), engines_of(last)
    head = engines[0]
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']} chip={head.get('chip')} "
          f"jax={installed('jax')} jaxlib={installed('jaxlib')} "
          f"libtpu={installed('libtpu')}")
    print(f"model={head['model']} quantize="
          f"{'n/a' if args.tiny else args.quantize} tp={args.tp} "
          f"replicas={args.replicas} slots={int(head['slots_total'])} "
          f"pages={int(head['pages_total'])}")
    for i, eng in enumerate(warm):
        # The engine's own record of its construction (engine_stats
        # `startup`; the server log's `engine started` line has it whole).
        startup = eng["startup"]
        comp, whole = startup["warmup_compile"], startup["compile"]
        slowest = max(startup["executables"], key=lambda row: row["seconds"],
                      default=None)
        print(f"engine[{i}] devices={[int(d) for d in eng['devices']]} "
              f"warmup_executables={int(comp.get('executables', 0))} "
              f"from_cache={int(comp.get('cache_hits', 0))} "
              f"fresh={int(comp.get('fresh_compiles', 0))} "
              f"mosaic_calls={_kernels(eng['warmup_mosaic_calls'])} "
              f"collectives={_ints(eng['warmup_collectives'])}")
        print(f"engine[{i}] startup_stage_seconds={startup['stages']} "
              f"trace_s={whole['trace_s']} lower_s={whole['lower_s']} "
              f"backend_s={whole['backend_s']} slowest_warm_call={slowest}")
        # Loaded compiled from the executable store, or built and written
        # to it (engine/executables.py); None with no cache directory.
        print(f"engine[{i}] executable_store={startup['executable_store']}")
    serving = (int(head["compiles"]["executables"])
               - int(warm[0]["compiles"]["executables"]))
    print(f"executables_built_while_serving={serving}")
    for eng in engines:
        for m in eng.get("device_memory", []):
            print(f"device[{int(m['id'])}] "
                  f"bytes_in_use={int(m['bytes_in_use'])} "
                  f"peak_bytes_in_use={int(m['peak_bytes_in_use'])} "
                  f"bytes_limit={int(m['bytes_limit'])}")
    print(f"requests={REQUESTS} tokens_returned={asked} "
          f"decode_blocks={total(engines, 'blocks_dispatched')} "
          f"requests_failed=0 engine_restarts=0")


def _ints(mapping: dict) -> dict:
    return {k: int(v) for k, v in mapping.items()}


def _kernels(calls: dict) -> dict:
    """{step: {kernel name: call sites}}, in a fixed order."""
    return {step: dict(sorted(_ints(calls[step]).items()))
            for step in sorted(calls)}


def stop_server(proc: subprocess.Popen, log_path: str) -> None:
    proc.send_signal(signal.SIGTERM)
    try:
        code = proc.wait(STOP_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            f"server did not stop within {STOP_TIMEOUT_S:.0f}s of SIGTERM"
        ) from None
    check(code == 0, f"server exited with code {code} after SIGTERM")
    with open(log_path, errors="replace") as f:
        check("server stopped" in f.read(),
              "server log has no 'server stopped' line")


def run(args) -> dict:
    import grpc

    from polykey_tpu.gateway.client import Client
    from polykey_tpu.gateway.config import Config
    from polykey_tpu.gateway.jsonlog import Logger

    explicit_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    check(args.tiny or not explicit_cpu,
          "JAX_PLATFORMS=cpu: the smoke needs a TPU (use --tiny for the "
          "CPU rehearsal)")

    os.makedirs(OUT_DIR, exist_ok=True)
    log_path = os.path.join(OUT_DIR, "chip_smoke_server.log")
    port = free_port()
    address = f"127.0.0.1:{port}"
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "polykey_tpu.gateway.server"],
            cwd=ROOT, env=server_env(args, port), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
    try:
        wait_serving(proc, address)
        print(f"cold_start_seconds={time.monotonic() - t0:.1f} "
              "(spawn to health SERVING: init + warm-up compiles)")
        client = Client(
            Config(server_address=address, timeout=30.0),
            Logger(stream=io.StringIO()),
        )
        try:
            first = engine_stats(client)
            asked = drive(client, random.Random(args.seed))
            last = engine_stats(client)
        except grpc.RpcError as e:
            raise SmokeFailure(
                f"request failed: {e.code().name}: {e.details()}"
            ) from e
        finally:
            client.close()
        device = check_stats(args, last, asked)
        report(args, device, first, last, asked)
        check(proc.poll() is None,
              f"server child died (code {proc.returncode}) while serving")
        stop_server(proc, log_path)
        check("jax" not in sys.modules,
              "the smoke's own process imported JAX (one process per chip)")
        return device
    except BaseException:
        sys.stderr.write(_log_tail(log_path))
        raise
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()


def _log_tail(log_path: str, lines: int = 30) -> str:
    try:
        with open(log_path, errors="replace") as f:
            tail = f.readlines()[-lines:]
    except OSError:
        return ""
    return "--- server log tail ---\n" + "".join(tail)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal at tiny-llama size")
    parser.add_argument("--model", default="llama-3-8b")
    parser.add_argument("--quantize", default="int8",
                        choices=("int8", "int4", "none"))
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--replicas", type=int, default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    try:
        device = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
