"""One benchmark run of one cell, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` for the cell, its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<mix>.json``) and its serving knobs
(``bench/cells/<cell>.json``), and the metric readers
(``bench/metrics/<metric>.py``) by name; nothing here names a cell.

What one run does:

1. set-up: weights drawn from the seed on the device by the
   configuration's architecture module (``bench/arch/<arch>.py``); the
   serving pod as ``serve --http`` builds it (``launch/serve.build_pod``:
   the cell file's ``instances`` in-process instances, default 1, one
   per chip; token-budget scheduler, prefix sharing, decode attention
   ``auto``, the controller on with the cell file's ``slo_latency`` where
   it gives one, the budget governor as the cell file sets it) behind
   an in-process ``Ingress`` on an ephemeral port. A pod of several
   instances is warmed up in process first, every warm-up group on every
   instance (``Orchestrator.submit_to``); the load generator
   (``loadgen.py``, a child process without JAX) primes the cell's
   streams and runs the lead-in. JAX's persistent compilation cache lives
   at ``.jax_cache/`` in the checkout, so only a checkout's first run
   compiles.
2. the window: ``--seconds`` of the cell's traffic through the HTTP door.
   With ``--trace 1`` a profiler trace is taken of a few seconds in its
   middle.
3. after the window: device memory peak read, server closed and freed,
   then the correctness check (``check.py``) on weights drawn again,
   against the architecture module's reference.

Prints the compilations inside the window on standard error, then each
number compared with its limit as the last lines there; the last line of
standard output is the result JSON. Exits 2, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check as CK  # noqa: E402
from bench import model as M  # noqa: E402
from bench import traffic as TF  # noqa: E402

TRACE_S = 3.0          # profiled sub-window, centred in the window
WARM_STEPS = 10 ** 5   # a pod warm-up group not served by then is a fault
CACHE_DIR = ROOT / ".jax_cache"


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read (``bench/metrics/*.py``)."""
    cell: dict
    conf: dict
    dm: dict                  # arch.dims(conf)
    arch: object              # the configuration's module (bench/arch/)
    mix: dict
    knobs: dict
    seed: int
    seconds: float
    setup_s: float
    w0: float                 # window, time.monotonic() seconds
    w1: float
    t_end: float              # when the load generator stopped
    records: list             # loadgen records (loadgen.py)
    steps: list               # spans.StepRecord per Engine.step, every
                              # instance, in the order the steps ran
    traces: list              # the ingress tracer's finished traces
    flight: list              # flight-recorder events
    peak: dict                # peaks.json entry of this device
    memory_peak_bytes: Optional[int]   # the fullest chip's peak
    compiles_in_window: list  # (monotonic, program, seconds) compiled or loaded
    gc_in_window: list        # (monotonic, generation, seconds) collections
    trace: object = None      # trace_reduce.Reduced, with --trace 1
    trace_host: tuple = None  # (t0, t1) monotonic of the traced sub-window

    def window_steps(self):
        """The steps wholly inside the window, an idle instance's left out
        (one instance is stepped only when it has work)."""
        return [s for s in self.steps
                if self.w0 <= s.t0 and s.t1 <= self.w1 and not s.idle]

    def traced_steps(self):
        a, b = self.trace_host
        return [s for s in self.steps
                if a <= s.t0 and s.t1 <= b and not s.idle]


def metric_names(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, run: Run):
    """Load ``bench/metrics/<name>.py`` and return its ``read(run)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _profile(out_dir: pathlib.Path, start: float, host: list):
    """Trace ``TRACE_S`` seconds from ``start`` (monotonic)."""
    import jax
    from bench import spans
    time.sleep(max(0.0, start - time.monotonic()))
    jax.profiler.start_trace(str(out_dir))
    with jax.profiler.TraceAnnotation(spans.WINDOW):
        host.append(time.monotonic())
        time.sleep(TRACE_S)
        host.append(time.monotonic())
    jax.profiler.stop_trace()


def serve(arch, cfg, dm, mix, knobs, seed, seconds, trace, devices, *,
          out_dir: pathlib.Path, engine_kw=None, keep_programs=False):
    """Set-up, the window, and the server torn down. ``devices`` are the
    chips the cell holds; ``arch`` (the configuration's module) makes the
    weights on the first. Returns the raw material of a ``Run`` (without
    the check), and the prompts sent.
    ``keep_programs`` keeps the compiled programs in memory afterwards,
    for a sweep's later runs in the same process."""
    from bench import spans

    counter = spans.CompileCounter()
    pauses = spans.GcPauses()
    try:
        return _serve(counter, pauses, arch, cfg, dm, mix, knobs, seed,
                      seconds, trace, devices, out_dir, engine_kw,
                      keep_programs)
    finally:
        counter.close()
        pauses.close()


def _serve(counter, pauses, arch, cfg, dm, mix, knobs, seed, seconds,
           trace, devices, out_dir, engine_kw, keep_programs):
    import jax
    from repro.launch import serve as SV
    from repro.serving import observe as OBS
    from repro.serving.ingress import Ingress
    from bench import spans

    w = arch.make_weights(dm, seed, devices[0])
    params = arch.program_params(w, cfg)
    del w
    M.check_layout(params, cfg)
    pod = {"slo": knobs["slo_latency"]} if "slo_latency" in knobs else {}
    args = SV.ServeConfig(
        arch=cfg.name, full_width=True, instances=knobs.get("instances", 1),
        max_batch=knobs["max_batch"], max_len=knobs["max_len"],
        token_budget=knobs["token_budget"], scheduler="budget", http=True,
        http_port=0, max_queue=knobs["max_queue"],
        govern_budget=knobs["govern_budget"], **pod).validate()
    orch = SV.build_pod(args, cfg, params, cfg.dtype,
                        n_blocks=knobs["pool_blocks"], **(engine_kw or {}))
    del params
    orch.flightrec = OBS.FlightRecorder(capacity=10 ** 7)
    spec = TF.generate(mix, knobs, dm["V"], seed, seconds)
    if len(orch.instances) > 1:
        _warm_pod(orch, spec.pop("warmup"))
    orch.tracer = OBS.Tracer(keep=10 ** 7)
    ing = Ingress(orch, host="127.0.0.1", port=0, model_id=cfg.name,
                  govern_budget=args.govern_budget)
    steps: list = []
    spans.instrument(ing, orch, steps)
    eng = orch.engines[0]
    log(f"{cfg.name}: {cfg.num_layers} layers, {len(orch.instances)} "
        f"instance(s), pool {knobs['pool_blocks']} blocks "
        f"({eng.pstate.pool_bytes()} bytes each), decode attention "
        f"{eng.paged_attn_impl}, max_batch {knobs['max_batch']}")
    out_dir.mkdir(parents=True, exist_ok=True)
    spec_path, res_path = out_dir / "spec.json", out_dir / "records.json"
    prompts = {r["id"]: r["prompt"] for r in spec["requests"]}
    host: list = []
    ing.start()
    try:
        spec["port"] = ing.port
        spec["t0"] = time.monotonic() + 0.2
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        _drive(spec_path, res_path, seconds, trace, out_dir, host)
        mem = _memory_peak(devices)
    finally:
        ing.close()
        orch.close()
    if ing.pump_error is not None:
        raise RuntimeError(f"the ingress pump died: {ing.pump_error!r}")
    with open(res_path) as f:
        res = json.load(f)
    raw = dict(w0=res["w0"], w1=res["w1"], t_end=res["t_end"],
               records=res["records"],
               steps=steps, traces=list(orch.tracer.finished),
               flight=orch.flightrec.events(), memory_peak_bytes=mem,
               compiles_in_window=counter.within(res["w0"], res["w1"]),
               gc_in_window=pauses.within(res["w0"], res["w1"]),
               trace_host=tuple(host) if host else None)
    del orch, ing, eng
    gc.collect()
    if not keep_programs:
        jax.clear_caches()
    return raw, prompts


def _warm_pod(orch, groups):
    """Set-up of a pod of several instances: each warm-up group
    (``traffic.warmup``) is submitted to every instance at once, by
    ``Orchestrator.submit_to``, and served to its end before the next, so
    every instance runs every shape the cell's traffic asks for (each
    instance is on its own chip, where a program compiled for another
    chip does not run)."""
    from repro.serving.request import RequestSpec, SamplingParams
    rid = 10 ** 9      # above the ingress's stream ids, which count from 1
    for group in groups:
        pending = set()
        for i in range(len(orch.instances)):
            for r in group:
                rid += 1
                pending.add(rid)
                orch.submit_to(i, RequestSpec(
                    rid=rid, prompt=np.asarray(r["prompt"], np.int32),
                    max_tokens=r["max_tokens"],
                    sampling=SamplingParams(temperature=r["temperature"],
                                            seed=r["seed"])))
        for _ in range(WARM_STEPS):
            pending -= {r.rid for r in orch.step()}
            if not pending:
                break
        else:
            raise RuntimeError(f"pod warm-up: {len(pending)} requests "
                               f"unfinished after {WARM_STEPS} steps")


def _memory_peak(devices) -> Optional[int]:
    """The peak bytes in use on the fullest of ``devices``, or None where
    the device keeps no such count."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _drive(spec_path, res_path, seconds, trace, out_dir, host):
    """Run the load generator to its end; with ``trace``, profile the
    middle of the window it reports."""
    child = subprocess.Popen(
        [sys.executable, str(BENCH / "loadgen.py"), str(spec_path),
         str(res_path)], stdout=subprocess.PIPE, text=True)
    profiler = None
    try:
        for line in child.stdout:
            ev = json.loads(line)
            if ev["event"] == "warm":
                log(f"warm-up: {ev['groups']} groups in "
                    f"{ev['seconds']:.1f} s")
            if ev["event"] == "window" and trace:
                start = ev["w0"] + (seconds - TRACE_S) / 2
                profiler = threading.Thread(
                    target=_profile, args=(out_dir / "trace", start, host))
                profiler.start()
        child.wait()
        if profiler is not None:
            profiler.join()
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             device, *, out_dir: pathlib.Path, overrides=None,
             engine_kw=None, check: bool = True, control: bool = False,
             keep_programs: bool = False, root: pathlib.Path = BENCH):
    """One run of ``cell``; returns ``(result, run, checks, extra)`` where
    ``extra`` holds the gaps read by the check (and, with ``control``, the
    control's gaps and the checks they give). ``overrides``
    ({"conf": {...}, "mix": {...}, "knobs": {...}}) resizes a cell for a
    rehearsal; the command line never passes any. ``root`` holds the
    cell's configuration, architecture, traffic and cell files."""
    import jax
    ov = overrides or {}
    conf = {**M.load_config(cell["config"], root), **ov.get("conf", {})}
    mix = {**TF.load("traffic", cell["traffic"], root), **ov.get("mix", {})}
    knobs = {**TF.load("cells", cell["name"], root), **ov.get("knobs", {})}
    arch = M.arch(conf, root)
    dm = arch.dims(conf)
    cfg = arch.model_config(conf, cell["config"])
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    peak = peaks.get(device.device_kind)
    if peak is None and device.platform == "tpu":
        raise KeyError(f"no peaks for device kind {device.device_kind!r} "
                       f"in bench/peaks.json")
    devices = jax.devices()[:cell["chips"]]
    raw, prompts = serve(arch, cfg, dm, mix, knobs, seed, seconds, trace,
                         devices, out_dir=out_dir, engine_kw=engine_kw,
                         keep_programs=keep_programs)
    run = Run(cell=cell, conf=conf, dm=dm, arch=arch, mix=mix, knobs=knobs,
              seed=seed, seconds=seconds, setup_s=raw["w0"] - T_START,
              peak=peak, **raw)
    comp = run.compiles_in_window
    log(f"compilations inside the window: {len(comp)} "
        f"({sum(c[2] for c in comp):.3f} s): "
        + ", ".join(f"{n} {t:.3f}" for _, n, t in comp[:40]))
    _describe(run)
    if trace:
        from bench import spans, trace_reduce
        path = next((out_dir / "trace").rglob("*.xplane.pb"))
        run.trace = trace_reduce.reduce(
            str(path), spans.WINDOW,
            {v for k, v in vars(spans).items()
             if k.isupper() and isinstance(v, str) and v != spans.WINDOW})
        shutil.rmtree(out_dir / "trace")     # reduced: keep the disk small
    checks = []
    extra = {}
    if check:
        moved = {e["rid"] for e in run.flight if e["kind"] == "migration"}
        sample = CK.sample(run.records, run.w0, mix["check_requests"], seed,
                           moved)
        _describe_sample(run, sample, moved)
        t = time.monotonic()
        w = arch.make_weights(dm, seed, device)
        g = CK.gaps(arch, w, dm, prompts, sample, knobs["max_len"])
        if control:
            cg = CK.gaps(arch, w, dm, prompts, sample, knobs["max_len"],
                         "fp8")
            extra["control_gaps"] = cg.tolist()
            extra["control_checks"] = CK.checks(cg, run.records,
                                                knobs["max_logit_gap"])
        del w
        extra["gaps"] = g.tolist()
        log(f"reference over {len(sample)} requests "
            f"({sum(len(r['tokens']) for r in sample)} served tokens) in "
            f"{time.monotonic() - t:.1f} s")
        checks = CK.checks(g, run.records, knobs["max_logit_gap"])
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metric_names(bench, cell["name"], kind):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted, failed = _attempts(run)
    result = {"correct": CK.correct(checks),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": run.memory_peak_bytes}}
    if trace:
        result["device"].update(busy_s=run.trace.busy_s,
                                window_s=run.trace.window_s)
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.top_gaps(10)}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, run, checks, extra


def _describe(run: Run):
    """What the window held, on standard error (information only)."""
    from bench.metrics._common import due_in_window, percentile
    st = run.window_steps()
    ms = [(s.t1 - s.t0) * 1e3 for s in st]
    rows = [len(s.decode_ctx) for s in st]
    due = due_in_window(run)
    ttft = sorted(round((r["times"][0] - r["due"]) * 1e3)
                  for r in due if r["times"])
    log(f"window {run.w1 - run.w0:.1f} s: {len(st)} engine steps, step ms "
        f"p50 {percentile(ms, 50)} p90 {percentile(ms, 90)} max "
        f"{max(ms, default=None)}; decode rows mean "
        f"{sum(rows) / max(len(rows), 1):.1f}; requests due {len(due)}, "
        f"TTFT ms {ttft}")
    gcs = run.gc_in_window
    log(f"garbage collections inside the window: {len(gcs)}, longest "
        f"{max((g[2] for g in gcs), default=0.0):.4f} s; generation 2: "
        + ", ".join(f"{g[0] - run.w0:.2f}+{g[2]:.4f}" for g in gcs
                    if g[1] == 2))
    for s in sorted(st, key=lambda s: s.t0 - s.t1)[:8]:
        log(f"long step at {s.t0 - run.w0:.2f} s: {(s.t1 - s.t0) * 1e3:.1f} "
            f"ms, {len(s.decode_ctx)} rows (context max "
            f"{max(s.decode_ctx, default=0)}), chunks {s.prefill}, packed "
            f"{s.packed}, blocks {s.blocks_in_use}")


def _describe_sample(run: Run, sample: list, moved: set):
    """Which of the checked requests migrated, and which finished after
    the controller's first scale-up, set-up included, replicated layers
    (information only)."""
    ups = [e["t"] for e in run.flight if e["kind"] == "controller"
           and (e.get("action") or "").startswith("scale-up")]
    log(f"checked requests: {len(sample)}; migrated "
        f"{sum(r.get('server_id') in moved for r in sample)} (of "
        f"{len(moved)} migrations in the run); finished after a scale-up "
        f"{sum(bool(ups) and r['times'][-1] > ups[0] for r in sample)}")


def _attempts(run: Run):
    """Requests due in the window (open loop) or sent in it (closed), and
    those of them refused, failed, or without a first token."""
    due = [r for r in run.records
           if run.w0 <= r["due"] <= run.w1 and r["phase"] != "prime"]
    failed = [r for r in due if r["status"] != 200 or r["error"]
              or not r["times"]]
    return len(due), len(failed)


def chip(cell: dict):
    """The first TPU device when JAX finds as many as the cell asks for
    (then the compilation cache is turned on at ``.jax_cache/``), else
    None after saying why."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"{cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s). No result.")
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devs[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == a.workload),
                None)
    if cell is None:
        log(f"no workload {a.workload!r} in BENCHMARK.json")
        return 2
    dev = chip(cell)
    if dev is None:
        return 2
    out_dir = BENCH / "out" / f"{a.workload}-{a.seed}-{a.trace}"
    result, run, checks, _ = run_cell(bench, cell, a.seed, a.seconds,
                                      bool(a.trace), dev,
                                      out_dir=out_dir)
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
