"""One run of one cell: the process that owns the chip.

It makes the weights from the seed, builds the engine itself and hands it
to examples/llm-server's build_app(config, engine=engine), so traffic goes
through the real HTTP/SSE handler and nothing of the program is edited. The
load generator is a child process (harness/loadgen_child.py) that never
imports JAX. From the program it takes the system under test and what that
already records: the step ledger (engine.steps), the flight recorder
(engine.recorder), the executor's compile table, the page allocator's
counts. Timing is the client's.
"""

import gc
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

from . import check, data, peaks, weights

HERE = os.path.dirname(os.path.abspath(__file__))


def emit(**fields) -> None:
    """Evidence: one JSON object a line, before the contract's last line."""
    print(json.dumps(fields), flush=True)


def program_root() -> str:
    import gofr_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(gofr_tpu.__file__)))


def out_dir() -> str:
    path = os.path.join(program_root(), "chiprun_out")
    os.makedirs(path, exist_ok=True)
    return path


def find_devices(chips: int, tiny: bool):
    """The accelerator the cell asks for, or no run at all: never a CPU
    number under a device's name. `--tiny` is the rehearsal on whatever
    JAX has (counts and the check only)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if tiny:
        return devices
    if platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found platform {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    peaks.of(devices[0].device_kind)        # an unknown kind is an error
    return devices


def llama_config(config: dict, dims: dict, kv_dtype=None):
    from gofr_tpu.models.llama import LlamaConfig

    engine = config["engine"]
    return LlamaConfig(
        vocab_size=dims["V"], dim=dims["D"], n_layers=dims["L"],
        n_heads=dims["H"], n_kv_heads=dims["Hkv"], ffn_dim=dims["F"],
        max_seq_len=int(config["max_position_embeddings"]),
        rope_theta=dims["theta"], rms_eps=dims["eps"],
        dtype=config["torch_dtype"], attn_impl=engine["attn_impl"],
        kv_dtype=kv_dtype)


def _app_config(port: int = 0):
    """examples/llm-server's own .env under the harness's settings: what a
    user who exports them and starts main.py gets. The ring capacities are
    the program's existing keys."""
    from gofr_tpu.config import EnvFile

    settings = {"HTTP_PORT": str(port), "METRICS_PORT": "0", "GRPC_PORT": "0",
                "LOG_LEVEL": "WARN", "STEP_LEDGER_CAPACITY": "16384",
                "FLIGHT_RECORDER_CAPACITY": "8192"}
    folder = os.path.join(program_root(), "examples", "llm-server", "configs")
    return EnvFile(folder, environ={**os.environ, **settings})


def _llm_server():
    path = os.path.join(program_root(), "examples", "llm-server", "main.py")
    spec = importlib.util.spec_from_file_location("benchmark_llm_server", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def warm_cell_programs(engine, cap: int) -> None:
    """The cell's own programs and no others: its prefill buckets at the
    admission widths its cap can produce, its decode table widths up to its
    longest context. The engine's warmup(k_variants=True) also compiles the
    widths above max_prefill_batch, which admission can then never ask for
    (96 x 1024 does not fit the chip), so the same loop is made here."""
    from gofr_tpu.tpu.engine import _admission_widths
    from gofr_tpu.tpu.paging import _pow2_at_least

    cap = cap or engine.n_slots
    with engine._state_lock:
        for bucket in engine.prefill_buckets:
            for k in sorted(_admission_widths(engine.n_slots)):
                if k <= cap:
                    engine._prefill_program(bucket, k)
        reach = engine.allocator.pages_for(engine.max_seq_len)
        for width in sorted({_pow2_at_least(p + 1)
                             for p in range(1, reach + 1)}):
            engine._decode_program_paged(width)
            if engine.decode_block_size > 1:
                engine._decode_program_paged(
                    width, max(1, engine.decode_block_size // 2))


class Server:
    """The system under test, from weights to a listening port."""

    def __init__(self, loaded: dict, seed: int, control=None):
        import jax

        from gofr_tpu.app import App
        from gofr_tpu.tpu.device import TPUClient
        from gofr_tpu.tpu.executor import Executor, enable_compile_cache
        from gofr_tpu.tpu.paging import PagedLLMEngine

        t0 = time.monotonic()
        self.loaded = loaded
        config, cell = loaded["config"], loaded["cell"]
        reference = data.reference_for(config)
        self.dims = reference.dims_of(config)
        cache_dir = enable_compile_cache()
        app_config = _app_config()
        # an App of the program's own, never started: it gives the engine
        # the logger, metrics and tracer that build_engine would give it,
        # so the loop pays for its counters as it does in a deployment
        shell = App(config=app_config)
        tpu = TPUClient(shell.config)
        shell.add_tpu(tpu)
        self.params = weights.make_params(self.dims, seed,
                                          config["torch_dtype"])
        jax.block_until_ready(self.params)
        served = self.params
        if control == "int8-weights":     # the program's own lower precision
            from gofr_tpu.models.llama import quantize_weights

            # it consumes the tree it is given: hand it a copy of the dicts
            served = quantize_weights(
                {**self.params, "layers": dict(self.params["layers"])})
        self.cfg = llama_config(
            config, self.dims, "int8" if control == "int8-kv" else None)
        sizing = config["engine"]
        self.engine = PagedLLMEngine(
            served, self.cfg, n_slots=int(sizing["n_slots"]),
            max_seq_len=int(sizing["max_seq_len"]),
            page_size=int(sizing["page_size"]), n_pages=int(sizing["n_pages"]),
            prefix_cache=bool(sizing["prefix_cache"]),
            prefill_buckets=tuple(cell["prefill_buckets"]),
            max_prefill_batch=int(cell.get("max_prefill_batch", 0)),
            decode_block_size=int(sizing["decode_block_size"]),
            pipeline_depth=int(sizing["pipeline_depth"]),
            executor=Executor(tpu, cache_dir=cache_dir),
            metrics=shell.container.metrics_manager, logger=shell.logger,
            tracer=shell.container.tracer)
        self.engine.start()
        self.init_s = time.monotonic() - t0
        t0 = time.monotonic()
        warm_cell_programs(self.engine, int(cell.get("max_prefill_batch", 0)))
        self.warmup_s = time.monotonic() - t0
        t0 = time.monotonic()
        self.app = _llm_server().build_app(config=app_config,
                                           engine=self.engine)
        self.app.add_tpu(tpu)
        # what build_engine registers for an engine it builds itself: finish
        # the active generations (bounded), then stop the loop
        engine = self.engine
        self.app.on_shutdown(lambda: (engine.drain(
            self.app.config.get_float("DRAIN_TIMEOUT", 30.0)), engine.stop()))
        self.app.start()
        self.port = self.app.http_port
        self.app_s = time.monotonic() - t0

    def compile_table(self) -> dict:
        return self.engine.executor.compile_table()

    def stop(self) -> float:
        """Drain, stop and free the pool: the reference runs after this, so
        `memory_peak_bytes` stays the program's."""
        t0 = time.monotonic()
        self.app.shutdown()
        thread = getattr(self.engine, "_thread", None)
        if thread is not None and thread.is_alive():
            raise RuntimeError("the engine loop outlived shutdown")
        for name in ("k_cache", "v_cache", "k_scale", "v_scale"):
            pool = getattr(self.engine, name, None)
            if pool is not None:
                pool.delete()
                setattr(self.engine, name, None)
        self.engine = self.app = None
        gc.collect()
        return time.monotonic() - t0


class PoolSampler(threading.Thread):
    """Pages in use, read from the allocator ten times a second (traced
    runs only)."""

    def __init__(self, engine):
        super().__init__(daemon=True)
        self.engine, self.samples, self.halt = engine, [], threading.Event()

    def run(self) -> None:
        while not self.halt.wait(0.1):
            self.samples.append((time.monotonic(),
                                 self.engine.allocator.used_pages))


def start_child(loaded: dict, seed: int, port: int, seconds: float):
    spec = {"mix": loaded["mix"], "seed": seed, "vocab": loaded["dims"]["V"],
            "port": port, "seconds": seconds}
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen_child.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    child.stdin.write(json.dumps(spec) + "\n")
    child.stdin.close()
    return child


def read_event(child, want: str, timeout_s: float) -> dict:
    """The child's next line, which has to be the event `want`."""
    box = {}

    def read():
        box["line"] = child.stdout.readline()

    reader = threading.Thread(target=read, daemon=True)
    reader.start()
    reader.join(timeout_s)
    if reader.is_alive() or not box.get("line"):
        child.kill()
        child.wait()
        raise RuntimeError(f"the load generator gave no {want!r} in "
                           f"{timeout_s:.0f} s")
    event = json.loads(box["line"])
    if event.get("event") != want:
        if event.get("event") == "result" and event.get("fatal"):
            child.wait()
            raise RuntimeError(f"load generator: {event['fatal']}")
        raise RuntimeError(f"expected {want!r}, the child said {event!r}")
    return event


def dump_debug(port: int, tag: str) -> None:
    """As chip_smoke.py does: on a failure keep what the loop was doing."""
    import http.client

    for path in ("/debug/steps", "/debug/hostprof", "/debug/requests",
                 "/debug/engine"):
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            conn.request("GET", path)
            body = conn.getresponse().read().decode("utf-8", "replace")
            conn.close()
        except Exception as exc:  # noqa: BLE001 - dump what can be had
            body = json.dumps({"error": f"{type(exc).__name__}: {exc}"})
        name = f"bench_fail_{tag}{path.replace('/', '_')}.json"
        with open(os.path.join(out_dir(), name), "w") as fp:
            fp.write(body)


def step_rows(engine, t_open: float, t_close: float):
    rows = []
    for rec in engine.steps.records(recent=1 << 20):
        if t_open <= rec.started_at < t_close:
            rows.append({"started_at": rec.started_at, "wall_s": rec.wall_s,
                         "idle_gap_s": rec.idle_gap_s, "phase": rec.phase,
                         "segments": dict(rec.segments),
                         "active_slots": rec.active_slots,
                         "tokens": rec.tokens, "inflight": rec.inflight,
                         "queue_depth": rec.queue_depth,
                         "dispatches": dict(rec.dispatches)})
    return rows


def ledger_totals(rows) -> dict:
    """Where the loop's time went in the window: by phase, the records,
    their wall seconds, tokens and segment seconds."""
    out = {}
    for row in rows:
        phase = out.setdefault(row["phase"], {
            "records": 0, "wall_s": 0.0, "idle_gap_s": 0.0, "tokens": 0,
            "segments": {}})
        phase["records"] += 1
        phase["wall_s"] += row["wall_s"]
        phase["idle_gap_s"] += row["idle_gap_s"]
        phase["tokens"] += row["tokens"]
        for name, seconds in row["segments"].items():
            if seconds:
                phase["segments"][name] = (phase["segments"].get(name, 0.0)
                                           + seconds)
    return {"phases": out}


def slots_served(engine) -> dict:
    """{the load generator's request index: the slot that served it}, from
    the flight recorder (the client sends the index as its trace id)."""
    return {int(rec["trace_id"], 16) - 1: rec["slot"]
            for rec in engine.recorder.snapshot()["recent"]
            if rec.get("trace_id") and rec.get("slot") is not None}


def memory_peak(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {"peak": max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
            "limit": max(int(s.get("bytes_limit", 0)) for s in stats)}


def measure(server: Server, seed: int, seconds: float, trace: bool) -> dict:
    """The window. Returns the run: what every metric is read from."""
    import jax

    loaded = server.loaded
    loaded["dims"] = server.dims
    engine = server.engine
    child = start_child(loaded, seed, server.port, seconds)
    run = {"loaded": loaded, "seconds": seconds, "seed": seed}
    sampler = None
    try:
        ramp_max = float(loaded["mix"].get("ramp", {}).get("max_s", 60)) + 30
        opened = read_event(child, "window_open", ramp_max)
        t_open, t_close = opened["t_open"], opened["t_close"]
        run["setup_table"] = server.compile_table()
        if trace:
            sampler = PoolSampler(engine)
            sampler.start()
            span = min(5.0, seconds / 2.0)
            start = (t_open + t_close) / 2.0 - span / 2.0
            time.sleep(max(0.0, start - time.monotonic()))
            trace_dir = os.path.join(out_dir(), "trace_" + loaded["cell"]["name"])
            # device planes only: no reader uses the host's frames, and the
            # Python tracer slows the loop and the handlers it is tracing
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = options.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            t_trace0 = time.monotonic()
            time.sleep(span)
            jax.profiler.stop_trace()
            # reduced once the server has stopped (run.py): reading it here
            # would hold this process's interpreter through the window
            run["traced"] = {"dir": trace_dir, "t0": t_trace0,
                             "t1": time.monotonic()}
        time.sleep(max(0.0, t_close - time.monotonic()))
        run["close_table"] = server.compile_table()
        drain = float(loaded["mix"].get("ramp", {}).get("drain_s", 0))
        result = read_event(child, "result", drain + 150.0)
        child.wait(timeout=30)
    except BaseException:
        dump_debug(server.port, loaded["cell"]["name"])
        if child.poll() is None:
            child.kill()
            child.wait()
        raise
    finally:
        if sampler is not None:
            sampler.halt.set()
            sampler.join()
    if result.get("fatal") or any(r.get("error") for r in result["records"]):
        dump_debug(server.port, loaded["cell"]["name"])
    if result.get("fatal"):
        raise RuntimeError(f"load generator: {result['fatal']}")
    run.update(result=result, t_open=t_open, t_close=t_close,
               steps=step_rows(engine, t_open, t_close),
               requests=engine.recorder.timeline_records(),
               slots=slots_served(engine), held=check.held(engine),
               pool={"usable": engine.allocator.n_pages - 1,
                     "samples": sampler.samples if sampler else []},
               memory=memory_peak(jax.local_devices()[
                   :int(loaded["config"]["deployment"]["chips"])]),
               engine={"n_slots": engine.n_slots,
                       "page_size": engine.page_size,
                       "decode_block_size": engine.decode_block_size,
                       "kv_bytes_per_token": 2 * server.dims["L"]
                       * server.dims["Hkv"] * server.dims["dh"]
                       * _kv_itemsize(server.cfg)})
    return run


def _kv_itemsize(cfg) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[
        cfg.kv_dtype or cfg.dtype]
