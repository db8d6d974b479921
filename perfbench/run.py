"""One run of one benchmark cell, on the machine this is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are looked up
by name in BENCHMARK.json and in the files under perfbench/ (configs/,
traffic/, metrics/): adding one is adding files and entries. The system
under test runs as one child process (perfbench/server_child.py: the
package's gateway and engine on a socket); this process never imports JAX,
makes the load, keeps the clock, and prints the result as the last line of
its standard output. `--tiny` rehearses the same code on the CPU at toy
size and reports platform=cpu and no device metric.

Set-up (process start -> window open) = engine construction and seeded
weights, loading or compiling every executable the cell's shapes use, the
served sample for the comparison with the plain reference (one greedy
request; among sampled companions on every slot where the configuration's
requests are sampled: `serve_sample`), and the clients' ramp. The window
opens once every client's first request streams.

WHAT A CONFIGURATION MAY BRING (the extension contract). A model whose
block is not the GQA decoder the harness was written around brings code
of its own as NEW files, each named by an optional key of its
configs/<name>.json and loaded by that name (perfbench/extension.py),
never by an `if` on a model. A file that names none of them is run
exactly as before these keys existed.

  "adapter": "<file>.py" -> perfbench/adapters/<file>.py, loaded by
    server_child.py. It may define any of these; one it leaves out is the
    harness's own function of that purpose:
      model_config(spec, tiny) -> the package's ModelConfig
          (absent: server_child.model_config_from, HF Mistral/Mixtral keys)
      engine_config(spec, tiny) -> the package's EngineConfig
          (absent: server_child.engine_config_from, the "engine" group)
      weights(spec, tiny, engine_config, model_cfg, seed) -> the parameter
          tree, or None for the engine's own seeded init (absent:
          server_child.made_weights; weights.hashed_int8(..., ones=<names>)
          fills the leaves it is told are norm gains with ones)
      release(engine) -> None: drop what the engine holds on the device
          besides its parameters, before the float32 reference runs
          (absent: server_child.release_pools, the two paged pools)
    `spec` is the configuration's JSON, `tiny` the CPU rehearsal; the
    sizes of the rehearsal are the adapter's to read from spec["tiny"].
  "reference": {"module": "<file>.py", <limits>} ->
    perfbench/references/<file>.py, the configuration's plain reference:
      forward(params, model_cfg, tokens) -> float32 logits [T, vocab]
    in plain jax.numpy, float32, precision "highest", importing nothing of
    the package, reading the served tree (reference.f32 reads an int8
    leaf). The teacher forcing, the margins and `judge` of
    perfbench/reference.py decide `correct` from it under the group's
    limits, as for every configuration. A module that defines
      compare(params, model_cfg, sample, limits) -> reference.judge's dict
    replaces the teacher forcing too. model_cfg.vocab_size of a sliced
    vocabulary is the slice. Absent: reference.forward. The verdict names
    the module that gave it ("module" in the result's `reference`).
  "costs": "<file>.py" -> perfbench/costs/<file>.py, the least bytes and
    operations of this architecture, from shapes, for one chip:
      decode_step_bytes(spec, live_tokens) -> bytes one decode step must
          read (read by metrics/decode_mbu.py in every cell)
      <kernel>(spec, ...) -> {"bytes": .., "flops": ..} of one call, one
          function for each name in "kernels", called by that kernel's
          reader metrics/<kernel>_roofline.py with the shapes it reads;
          kernel_costs.roofline_seconds turns it into the least time.
      A model that holds experts a step may leave unchosen also defines
      held_experts(spec) -> the experts one layer holds, and gives
      decode_step_bytes and moe_held_experts a keyword `hit_share` (1.0:
      every held expert) that scales the held experts' matrices alone;
      the readers pass it only where the server's engine_stats carry
      `held_experts_hit` and `held_expert_calls` (counters.held_hit_share).
    It runs in this process, which never imports JAX: plain Python.
    Absent: perfbench/kernel_costs.py itself (kernel_costs.for_spec).
  "sampling": {"temperature": .., "top_k": .., "top_p": ..} -> the request
    parameters of the served API that every request of the window carries,
    with a sampling seed of its own (traffic.Plan.sampling), under any
    traffic file; the child warms the sampled variant of every step, and
    `correct` also holds one sampled companion's draws to the reference's
    top-k (reference.judge_sampled). Absent: greedy requests.
  "kernels": ["<name>", ...] -> device operations whose name starts with
    <name> are summed under trace["kernels"][<name>] by trace_reduce.py,
    after the four kernels it knows (first match wins, those four first,
    so no configuration takes time from another's reader).

A metric with no "workloads" list in BENCHMARK.json is read in every cell
that reports the metric it moves, a new configuration's too: give a
reader of one kernel or one layer type the list of the cells that have it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import extension  # noqa: E402

START_TIMEOUT_S = 1100.0     # a first run compiles every executable
RAMP_TIMEOUT_S = 120.0
STOP_TIMEOUT_S = 180.0
PROFILER_TIMEOUT_S = 200.0   # stopping a capture on four chips takes a while
SAMPLE_PROMPT_TOKENS = 24    # <= the MoE dispatch capacity of a lone prefill
SAMPLE_OUTPUT_TOKENS = 32


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero, print no line."""


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchFailure(f"BENCHMARK.json has no {what} named {name!r}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(tiny: bool, chips: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # One fixed cache directory inside the checkout unless the machine
    # provides one; the package then sets none of its own.
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    env["POLYKEY_METRICS_PORT"] = "0"
    if tiny:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    elif env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        raise BenchFailure("JAX_PLATFORMS=cpu: a cell runs on the TPU "
                           "(--tiny is the CPU rehearsal)")
    return env


def wait_serving(proc: subprocess.Popen, address: str) -> None:
    import grpc

    from polykey_tpu.proto import health_v1_pb2 as health_pb
    from polykey_tpu.proto.health_v1_grpc import HealthStub

    deadline = time.monotonic() + START_TIMEOUT_S
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise BenchFailure(
                f"server child exited with code {proc.returncode} "
                "before serving")
        # A new channel for every probe: one channel that has failed to
        # connect for half a minute backs off for many seconds, which
        # would add a random wait to every run's set-up.
        with grpc.insecure_channel(address) as channel:
            try:
                reply = HealthStub(channel).Check(
                    health_pb.HealthCheckRequest(service=""), timeout=2.0)
                if reply.status == health_pb.HealthCheckResponse.SERVING:
                    return
            except grpc.RpcError:
                pass
        time.sleep(0.2)
    raise BenchFailure(f"server not SERVING within {START_TIMEOUT_S:.0f}s")


class Tools:
    """The unary tools of the served API this harness calls."""

    def __init__(self, address: str):
        import grpc

        from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

        self.channel = grpc.insecure_channel(address)
        self.stub = PolykeyServiceStub(self.channel)

    def call(self, tool: str, **params) -> dict:
        from google.protobuf.json_format import MessageToDict

        from polykey_tpu.proto import polykey_v2_pb2 as pk

        request = pk.ExecuteToolRequest(tool_name=tool)
        if params:
            request.parameters.update(params)
        reply = self.stub.ExecuteTool(request, timeout=PROFILER_TIMEOUT_S)
        if reply.status.code != 200:
            raise BenchFailure(f"{tool} status {reply.status}")
        return MessageToDict(reply.struct_output)

    def stats(self) -> dict:
        stats = self.call("engine_stats")
        stats["_at"] = time.monotonic()
        return stats

    def close(self) -> None:
        self.channel.close()


def sample_ids(prompt: str, text: str) -> dict:
    return {"prompt_ids": [1] + [3 + b for b in prompt.encode()],
            "output_ids": [3 + b for b in text.encode()]}


def serve_sample(address: str, seed: int, out_dir: str, plan=None) -> list:
    """The served greedy sample the child later compares with the plain
    reference: one short request, alone on the idle engine where the
    configuration's requests are greedy; the records of every request
    sent, the sample's first.

    Where they are sampled (`plan.sampled`) every step of the window runs
    its SAMPLED variant (the engine keys the variant on the batch: one
    sampled lane and all lanes take it, a greedy lane's argmax included),
    so the sample is taken from those programs with every slot in use, as
    the window has them: `plan.clients` - 2 sampled companions stream
    first, four times as long; once each has its first token, the greedy
    sample and one more sampled companion of its own size are sent
    together. The greedy request's tokens then come from the sampled
    decode step among 63 sampled lanes, and the last companion's tokens,
    drawn under the configuration's own parameters, are kept beside them
    (`sampled` in sample.json, for reference.judge_sampled)."""
    import random

    import grpc

    import loadgen
    import traffic
    from polykey_tpu.proto.polykey_v2_grpc import PolykeyServiceStub

    def prompt_of(key: str) -> str:
        rng = random.Random(key)
        return "".join(rng.choices(traffic.ALPHABET,
                                   k=SAMPLE_PROMPT_TOKENS - 1))

    prompt = prompt_of(f"{seed}/sample")
    record = loadgen.new_record(-1, 0, SAMPLE_PROMPT_TOKENS,
                                SAMPLE_OUTPUT_TOKENS)
    others, threads = [], []
    with grpc.insecure_channel(
            address, options=loadgen.CHANNEL_OPTIONS) as channel:
        stub = PolykeyServiceStub(channel)

        def send(rec, text, tokens, sampling):
            thread = threading.Thread(
                target=loadgen.stream, args=(stub, text, tokens, rec),
                kwargs={"keep_text": True, "sampling": sampling}, daemon=True)
            thread.start()
            threads.append(thread)

        if plan is not None and plan.sampled:
            for i in range(1, plan.clients - 1):
                rec = loadgen.new_record(-1, i, SAMPLE_PROMPT_TOKENS,
                                         4 * SAMPLE_OUTPUT_TOKENS)
                others.append(rec)
                send(rec, prompt_of(f"{seed}/sample/{i}"), rec["asked"],
                     plan.sampling(-1, i))
            deadline = time.monotonic() + RAMP_TIMEOUT_S
            while not all(r["times"] or r["error"] or r["final"]
                          for r in others):
                if time.monotonic() > deadline:
                    raise BenchFailure("the sample's companions did not all "
                                       "start streaming")
                time.sleep(0.005)
            drawn = loadgen.new_record(-1, plan.clients - 1,
                                       SAMPLE_PROMPT_TOKENS,
                                       SAMPLE_OUTPUT_TOKENS)
            others.append(drawn)
            drawn_prompt = prompt_of(f"{seed}/sample/drawn")
            send(drawn, drawn_prompt, drawn["asked"],
                 plan.sampling(-1, plan.clients - 1))
        send(record, prompt, SAMPLE_OUTPUT_TOKENS, None)
        for thread in threads:
            thread.join(loadgen.REQUEST_TIMEOUT_S + 10.0)
        if any(thread.is_alive() for thread in threads):
            raise BenchFailure("a request of the served sample did not end")
    sample = {**sample_ids(prompt, "".join(record.pop("text"))),
              "allowed_first": traffic.FIRST_ID,
              "allowed_last": traffic.LAST_ID}
    texts = ["".join(rec.pop("text")) for rec in others]
    if others and plan.sampled.get("top_k", 0) > 0:
        sample["sampled"] = {**sample_ids(drawn_prompt, texts[-1]),
                             "top_k": plan.sampled["top_k"]}
    with open(os.path.join(out_dir, "sample.json"), "w") as f:
        json.dump(sample, f)
    return [record] + others


def request_faults(record: dict) -> list:
    """Why a request that ended by itself is not a correct answer."""
    if record["error"] == "cancelled":
        return []               # cut by the harness at the window's end
    if record["error"]:
        return [record["error"]]
    faults = []
    usage = record["usage"] or {}
    if record["status"] != 200:
        faults.append(f"status {record['status']}")
    if sum(record["counts"]) != record["asked"]:
        faults.append(f"streamed {sum(record['counts'])} of "
                      f"{record['asked']} tokens")
    if usage.get("completion_tokens") != record["asked"]:
        faults.append(f"Usage.completion_tokens {usage.get('completion_tokens')}")
    if usage.get("prompt_tokens") != record["prompt_tokens"]:
        faults.append(f"Usage.prompt_tokens {usage.get('prompt_tokens')}")
    if not usage.get("ttft_ms", 0) > 0:
        faults.append("Usage.ttft_ms not filled")
    return faults


def check_text(name: str, value: int, limit: int) -> str:
    return f"{name} {value} (limit {limit})"


def decide_correct(stats_ready: dict, stats_close: dict, stats_end: dict,
                   requests: list, reference: dict | None) -> dict:
    """Whether the run's outputs are correct; `checks` holds each number
    compared beside its limit, `why` the checks that failed.

    `requests_failed` includes cancellations, and the engine counts one
    when it next processes a block. So failures inside the window are read
    at its close (`stats_close`), while every client still streams; from
    then to `stats_end` the count may rise by no more than the streams the
    harness itself cancelled. A restart at any time is a fault.
    """
    sent = [r for r in requests if r["send"] is not None]
    faults = [(r["client"], r["index"], request_faults(r)) for r in sent]
    faults = [f for f in faults if f[2]]
    cancelled = sum(r["error"] == "cancelled" for r in sent)
    failed = [int(s.get("requests_failed", 0))
              for s in (stats_ready, stats_close, stats_end)]
    checks = {
        "request_faults": (len(faults), 0),
        "engine_restarts": (int(stats_end.get("engine_restarts", 0)), 0),
        "failed_in_window": (failed[1] - failed[0], 0),
        "failed_after_close": (failed[2] - failed[1], cancelled),
    }
    why = [check_text(name, *pair) for name, pair in checks.items()
           if not 0 <= pair[0] <= pair[1]]
    if reference is None:
        why.append("no verdict of the plain reference")
    elif not reference["ok"]:
        why.append("the served sample disagrees with the plain reference: "
                   + "; ".join(reference.get("why") or
                               [reference.get("error", "no clause named")]))
    return {"correct": not why, "why": why, "checks": checks,
            "attempted": len(sent), "faults": faults}


def start_capture(side, traced: dict, trace_dir: str) -> None:
    side.call("engine_profile", action="start", log_dir=trace_dir)
    traced["start"] = time.monotonic()
    traced["stats_start"] = side.stats()


def stop_capture(side, traced: dict) -> None:
    """The end of the profiler capture as the host saw it (samples' meta
    `traced`). `start` .. `stop` is the stretch the readers count over:
    `start` is stamped once the profiler's start call has returned, `stop`
    BEFORE its stop call is made — the capture ends there, while the call
    goes on writing the trace for most of a minute with the clients still
    served — each beside a reading of engine_stats (`stats_start`,
    `stats_stop`). How long the stop call took is kept beside them and
    bounds nothing; `stop_call_s` is also the sign that the capture
    ended."""
    traced["stats_stop"] = side.stats()
    traced["stop"] = time.monotonic()
    side.call("engine_profile", action="stop")
    traced["stop_call_s"] = time.monotonic() - traced["stop"]


class Context:
    """What a metric reader may read (perfbench/metrics/<name>.py)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def read_metric(name: str, ctx: Context):
    try:
        module = extension.load("metrics", f"{name}.py")
    except (FileNotFoundError, ValueError):
        raise BenchFailure(f"no reader perfbench/metrics/{name}.py") from None
    return module.read(ctx)


def metrics_for(manifest: dict, cell: str, group: str) -> list:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def reduce_trace(trace_dir: str, out_path: str, kernels: list) -> dict | None:
    """The reduction runs in a process of its own, on the CPU, after the
    server has released the chip. `kernels`: the configuration's own
    kernel names, summed beside the four trace_reduce.py knows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"),
         trace_dir, out_path, *kernels],
        env=env, capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-2000:])
        return None
    with open(out_path) as f:
        return json.load(f)


def run(args) -> dict:
    if not os.path.isdir(os.path.join(ROOT, "polykey_tpu")):
        raise BenchFailure("the system under test (polykey_tpu/) is not "
                           "in this checkout")
    import loadgen
    import peaks
    import traffic as traffic_mod

    manifest = load_manifest()
    cell = find(manifest["workloads"], args.workload, "workload")
    config_entry = find(manifest["configs"], cell["config"], "config")
    config_path = os.path.join(ROOT, config_entry["file"])
    with open(config_path) as f:
        spec = json.load(f)
    traffic = traffic_mod.scaled(traffic_mod.load(cell["traffic"]), args.tiny)
    plan = traffic_mod.Plan(traffic, args.seed, spec.get("sampling"))

    out_dir = os.path.join(HERE, "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    for stale in ("sample.json", "reference.json"):
        if os.path.exists(os.path.join(out_dir, stale)):
            os.remove(os.path.join(out_dir, stale))
    tag = f"seed{args.seed}.trace{args.trace}"
    log_path = os.path.join(out_dir, f"{tag}.server.log")
    address = f"127.0.0.1:{free_port()}"
    command = [sys.executable, os.path.join(HERE, "server_child.py"),
               "--config", config_path, "--seed", str(args.seed),
               "--address", address, "--out", out_dir]
    if args.tiny:
        command.append("--tiny")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            command, cwd=ROOT, env=child_env(args.tiny, cell["chips"]),
            stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )
    tools = None
    loop = None
    try:
        wait_serving(proc, address)
        tools = Tools(address)
        stats_ready = tools.stats()
        platform = stats_ready["platform"]
        count = int(stats_ready["device_count"])
        if not args.tiny and (platform != "tpu" or count < cell["chips"]):
            raise BenchFailure(
                f"the cell asks for {cell['chips']} TPU chip(s); JAX found "
                f"{count} device(s) of platform {platform!r}")
        used = len(stats_ready["devices"])
        peak_row = None if args.tiny else peaks.row(stats_ready["device_kind"])

        sample = serve_sample(address, args.seed, out_dir, plan)

        loop = loadgen.ClosedLoop(address, plan)
        loop.start()
        deadline = time.monotonic() + RAMP_TIMEOUT_S
        while not loop.all_streaming():
            if time.monotonic() > deadline or proc.poll() is not None:
                raise BenchFailure("the clients' first requests did not all "
                                   "start streaming")
            time.sleep(0.005)
        t_open = time.monotonic()
        setup_s = t_open - T_START
        stats_open = tools.stats()

        polls: list = []
        trace_dir = os.path.join(out_dir, f"{tag}.trace")
        traced = {"start": None, "stop": None, "stop_call_s": None}
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # The configuration says how long a capture it can afford.
            trace_len = min(float(spec["trace_seconds"]), args.seconds / 2)
            trace_at = t_open + (args.seconds - trace_len) / 2
            stop_polling = threading.Event()

            def side_work() -> None:
                side = Tools(address)
                try:
                    while not stop_polling.is_set():
                        now = time.monotonic()
                        if traced["start"] is None and now >= trace_at:
                            start_capture(side, traced, trace_dir)
                        elif (traced["start"] is not None
                              and traced["stop"] is None
                              and now >= traced["start"] + trace_len):
                            stop_capture(side, traced)
                        else:
                            polls.append(side.stats())
                        stop_polling.wait(0.5)
                    # The window closed on an open capture (a rehearsal of
                    # a few seconds on a loaded machine): it is ended all
                    # the same, never left running.
                    if traced["start"] is not None and traced["stop"] is None:
                        stop_capture(side, traced)
                finally:
                    side.close()

            poller = threading.Thread(target=side_work, daemon=True)
            poller.start()

        while time.monotonic() < t_open + args.seconds:
            if proc.poll() is not None:
                raise BenchFailure("the server child died inside the window")
            time.sleep(min(0.2, max(0.0, t_open + args.seconds - time.monotonic())))
        t_close = time.monotonic()
        stats_close = tools.stats()
        if args.trace:
            stop_polling.set()
            poller.join(timeout=PROFILER_TIMEOUT_S + 10.0)
            if poller.is_alive() or traced["stop_call_s"] is None:
                raise BenchFailure("the profiler capture did not end")
        loop.stop()
        if loop.alive():
            raise BenchFailure("a client thread did not end")
        stats_end = tools.stats()
        tools.close()
        tools = None

        proc.send_signal(signal.SIGTERM)
        try:
            code = proc.wait(STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise BenchFailure("the server did not stop after SIGTERM") from None
        if code != 0:
            raise BenchFailure(f"the server exited with code {code}")
    except BaseException:
        sys.stderr.write(_log_tail(log_path))
        raise
    finally:
        if tools is not None:
            tools.close()
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()

    reference = None
    ref_path = os.path.join(out_dir, "reference.json")
    if os.path.exists(ref_path):
        with open(ref_path) as f:
            reference = json.load(f)

    requests = sample + loop.records
    verdict = decide_correct(stats_ready, stats_close, stats_end, requests,
                             reference)
    checks = verdict["checks"]
    samples = {
        "meta": {
            "workload": args.workload, "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace, "tiny": args.tiny,
            "t_start": T_START, "t_open": t_open, "t_close": t_close,
            "setup_s": setup_s, "platform": platform,
            "device_kind": stats_ready["device_kind"], "devices": used,
            "generator_late_ms_max": 1000.0 * max(loop.late_s, default=0.0),
            "traced": traced,
            "failed_in_window": checks["failed_in_window"][0],
            "failed_after_close": checks["failed_after_close"][0],
            "cancelled_by_harness": checks["failed_after_close"][1],
            # Every run's own count (the metric of that name is a traced
            # run's): a stall in a cold run's window is or is not a compile.
            "compiles_in_window": read_metric(
                "compiles_in_window",
                Context(stats_open=stats_open, stats_close=stats_close)),
            "why_incorrect": verdict["why"],
            "reference_margins": (reference or {}).get("margins"),
        },
        "requests": requests,
    }
    with gzip.open(os.path.join(out_dir, f"{tag}.samples.json.gz"), "wt") as f:
        json.dump(samples, f)
    print("perfbench: " + ", ".join(
        check_text(name, *pair) for name, pair in checks.items()),
        file=sys.stderr)
    if reference is not None and "checks" in reference:
        print("perfbench: reference: " + reference["checks"], file=sys.stderr)
    for reason in verdict["why"]:
        print(f"perfbench: not correct: {reason}", file=sys.stderr)
    faults = verdict["faults"]

    trace = None
    if args.trace and platform == "tpu":
        trace = reduce_trace(trace_dir,
                             os.path.join(out_dir, f"{tag}.trace.json"),
                             spec.get("kernels", []))
        if trace is None or not trace.get("busy_s", 0) > 0:
            raise BenchFailure("the traced window holds no device operation")
    ctx = Context(
        samples=samples, spec=spec, traffic=traffic, cell=cell, tiny=args.tiny,
        stats_ready=stats_ready, stats_open=stats_open,
        stats_close=stats_close, stats_end=stats_end, polls=polls,
        trace=trace, peaks=peak_row, setup_s=setup_s, chips=used,
    )
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in metrics_for(manifest, args.workload, group):
        if entry["source"] == "device_trace" and trace is None:
            continue            # the CPU rehearsal reports no device metric
        value = read_metric(entry["name"], ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    memory = [int(m["peak_bytes_in_use"])
              for m in stats_end.get("device_memory", [])]
    device = {"platform": platform, "kind": stats_ready["device_kind"],
              "count": used, "memory_peak_bytes": max(memory, default=0)}
    result = {
        "correct": verdict["correct"], "attempted": verdict["attempted"],
        "failed": len(faults), "metrics": metrics, "device": device,
        "generator_late_ms_max": samples["meta"]["generator_late_ms_max"],
        "reference": reference, "faults": faults[:5],
        "engine_restarts": checks["engine_restarts"][0],
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    return result


def _log_tail(log_path: str, lines: int = 40) -> str:
    try:
        with open(log_path, errors="replace") as f:
            tail = f.readlines()[-lines:]
    except OSError:
        return ""
    return "--- server log tail ---\n" + "".join(tail)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal at toy size (platform=cpu)")
    args = parser.parse_args(argv)
    try:
        result = run(args)
    except BenchFailure as e:
        print(f"perfbench FAILED: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("perfbench FAILED: the load generator imported JAX",
              file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
