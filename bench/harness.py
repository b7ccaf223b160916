"""One run of one cell, from set-up to the result line.

``run_cell`` makes the weights from the seed, builds the engine through
the serving CLI's own functions (``launch/serve.py``: ``build_parser``,
``build_engine``), warms every step program the traffic uses, replays the
cell's open-loop arrivals through ``Engine.add_request`` and
``Engine.step`` (the CLI's synchronous loop) for a lead-in and then the
measured window, drains the requests that were due in the window, reads
the metrics, frees the engine and decides ``correct`` against the plain
reference.  Everything a cell is made of is found by name:

  bench/configs/<config>.json    widths, serve flags, reference module
  bench/reference/<module>.py    plain reference and seeded weights
  bench/traffic/<mix>.json       generator parameters
  bench/cells/<cell>.json        arrival rate and correctness limits
  bench/metrics/<metric>.py      one reader per metric
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import inspect
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import correctness
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
DRAIN_LIMIT_S = 120.0       # a request due in the window and not finished
                            # this long after the window closes has failed:
                            # the longest chat answer, 512 tokens, takes
                            # about 50 s at a 100 ms decode step
TRACE_S = 15.0              # device-trace length in a --trace 1 run: long
                            # enough to hold a prefill at 0.4 arrivals/s


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    conf: dict
    mix: dict
    spec: dict                  # bench/cells/<cell>.json
    end_to_end: list[str]
    per_layer: list[str]
    chips: int


def _applies(metric: dict, cell: str, reported: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def resolve(bench: dict, workload: str) -> Cell:
    """The cell named ``workload`` with its files and its metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(os.path.dirname(HERE), confs[w["config"]]["file"])
    e2e = [m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    layer = [m["name"] for m in bench["per_layer"]
             if _applies(m, workload, set(e2e))]
    return Cell(name=workload, conf=conf,
                mix=traffic.load_mix(w["traffic"]),
                spec=load_json(HERE, "cells", workload + ".json"),
                end_to_end=e2e, per_layer=layer, chips=w["chips"])


# ---------------------------------------------------------------------------
# what the metric readers read
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    """One request of the schedule as the harness saw it (host clock,
    seconds from the schedule's start)."""
    due: float
    prompt: list[int]
    max_new: int
    rid: int = -1
    submit: float = float("nan")
    tokens: list[int] = dataclasses.field(default_factory=list)
    times: list[float] = dataclasses.field(default_factory=list)
    done: bool = False
    queue_wait_s: float = float("nan")      # the engine's: submit -> admit
    finish_reason: str = ""


@dataclasses.dataclass
class RunData:
    """Everything one run measured; each metric reader takes what it
    needs and returns None where it finds nothing to read."""
    widths: dict
    peak: dict
    block_size: int
    window: tuple[float, float]             # schedule seconds [t0, t1)
    reqs: list[Req]
    setup_s: float
    counters: dict[str, float]              # engine counters, window delta
    phases: dict[str, tuple[float, int]]    # obs phase (total s, count)
    calls: dict[str, list]                  # step-program arguments seen
    trace: dict | None = None               # trace_reduce.summary

    def in_window(self) -> list[Req]:
        t0, t1 = self.window
        return [r for r in self.reqs if t0 <= r.due < t1]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _engine(cell: Cell, ref, params, telemetry):
    from repro.configs.base import ArchConfig
    from repro.launch.serve import build_engine, build_parser
    from repro.models import build
    arch = ArchConfig(**ref.program_arch(cell.conf))
    model = build(arch)
    argv = ["--arch", arch.name, *cell.conf["serve"]["flags"]]
    args = build_parser().parse_args(argv)
    return build_engine(arch, model, params, args, telemetry=telemetry)


def _check_layout(eng, params) -> None:
    """The seeded weights have the tree, shapes and dtypes the program's
    own initializer makes."""
    import jax
    want = jax.eval_shape(eng.model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("seeded weights do not match the program's layout")


def warm(eng, vocab: int, seed: int) -> None:
    """Compile every program the traffic drives: chunked prefill over
    several chunks, decode, a prefix-cache hit and a full-prompt hit that
    copies a shared block on write.  Then clear all request state."""
    rng = np.random.default_rng(seed)
    C, bs = eng.cfg.chunk_size, eng.cfg.block_size
    a = rng.integers(0, vocab, 2 * C + 7).tolist()
    eng.add_request(a, max_new_tokens=4)
    eng.run()
    eng.add_request(a[:2 * bs], max_new_tokens=2)          # full hit: COW
    eng.add_request(a[:C] + rng.integers(0, vocab, 20).tolist(),
                    max_new_tokens=2)
    eng.run()
    eng.reset()


def _counters(eng) -> dict[str, float]:
    c = {k.split("/", 1)[1]: float(v) for k, v in
         eng.obs.registry.counter_values("serve/").items()}
    c["prefix_hits"] = float(eng.cache_host.prefix_hits)
    c["prefix_lookups"] = float(eng.cache_host.prefix_lookups)
    return c


def _phases(eng) -> dict[str, tuple[float, int]]:
    return {k.split("/", 1)[1]: (h.total, h.count) for k, h in
            eng.obs.registry.histograms.items() if k.startswith("phase/")}


class _CallLog:
    """Records the arguments of the engine's decode and prefill step
    programs while on (device arrays, read after the window).  Arguments
    are matched by name against the signatures of ``_step_impl`` and
    ``_prefill_impl``; where a name is missing the step is not wrapped, a
    note goes to standard error and the metrics that need it stay silent."""

    WANT = {"decode": ("_step_fn", "_step_impl", ("positions", "active")),
            "prefill": ("_prefill_fn", "_prefill_impl", ("positions",
                                                          "valid"))}

    def __init__(self, eng):
        self.on = False
        self.calls: dict[str, list] = {"decode": [], "prefill": []}
        for kind, (attr, impl, names) in self.WANT.items():
            fn, sig = getattr(eng, attr, None), None
            if hasattr(eng, impl):
                sig = inspect.signature(getattr(eng, impl))
            if fn is None or sig is None or not set(names) <= set(
                    sig.parameters):
                print(f"bench: {impl} has no parameters {names}; the "
                      f"{kind} call metrics stay silent", file=sys.stderr)
                continue
            setattr(eng, attr, self._wrap(fn, kind, sig, names))

    def _wrap(self, fn, kind, sig, names):
        def call(*args, **kwargs):
            if self.on:
                bound = sig.bind(*args, **kwargs).arguments
                self.calls[kind].append(tuple(bound[n] for n in names))
            return fn(*args, **kwargs)
        return call

    def read(self) -> dict[str, list]:
        """decode: (kv_lens of live rows,); prefill: (starts, valids) of
        rows with tokens."""
        out: dict[str, list] = {"decode": [], "prefill": []}
        for positions, active in self.calls["decode"]:
            p, a = np.asarray(positions), np.asarray(active)
            out["decode"].append((p[a] + 1,))
        for pos, valid in self.calls["prefill"]:
            p, v = np.asarray(pos), np.asarray(valid)
            live = v > 0
            out["prefill"].append((p[live, 0], v[live]))
        return out


def drive(eng, reqs: list[traffic.Request], lead_in: float, seconds: float,
          tracer=None, drain_limit_s: float = DRAIN_LIMIT_S
          ) -> tuple[list[Req], dict]:
    """The open loop: submit each request when it is due, step the engine
    while it has work, measure [lead_in, lead_in + seconds) and drain
    the requests due in it.  Times are perf_counter seconds from the
    schedule's start."""
    import jax
    clock = time.perf_counter
    ann = jax.profiler.TraceAnnotation
    rs = [Req(due=r.due, prompt=r.prompt, max_new=r.max_new) for r in reqs]
    t0, t1 = lead_in, lead_in + seconds
    marks: dict = {}
    start = clock()
    nxt = 0
    pending = None                      # requests due in the window, open

    def on_token(r: Req):
        def cb(tok, done):
            if tok is not None:
                r.tokens.append(int(tok))
                r.times.append(clock() - start)
            r.done = r.done or done
        return cb

    while True:
        now = clock() - start
        if "t0" not in marks and now >= t0:
            marks["t0"] = (now, _counters(eng), _phases(eng))
            marks["backlog0"] = _backlog(eng)
            marks["waiting0"] = len(eng.scheduler.waiting)
            if tracer is not None:
                tracer.start()
        if tracer is not None and tracer.active and now >= t0 + tracer.length:
            tracer.stop()
        if "t1" not in marks and now >= t1:
            marks["t1"] = (now, _counters(eng), _phases(eng))
            marks["backlog1"] = _backlog(eng)
            marks["waiting1"] = len(eng.scheduler.waiting)
            if tracer is not None and tracer.active:
                tracer.stop()
            pending = [r for r in rs if t0 <= r.due < t1]
        if pending is not None:
            pending = [r for r in pending if not r.done]
            if not pending or now >= t1 + drain_limit_s:
                marks["drain_s"] = now - t1
                break
        with ann("bench.submit"):
            while nxt < len(rs) and rs[nxt].due <= now:
                r, q = rs[nxt], reqs[nxt]
                r.submit = clock() - start
                r.rid = eng.add_request(q.prompt, max_new_tokens=q.max_new,
                                        temperature=q.temperature,
                                        on_token=on_token(r))
                nxt += 1
        if eng.scheduler.has_work:
            with ann("bench.step"):
                eng.step()
        elif nxt < len(rs):
            with ann("bench.idle"):
                time.sleep(max(0.0, min(rs[nxt].due - (clock() - start),
                                        0.002)))
        elif pending is None:
            with ann("bench.idle"):
                time.sleep(0.002)
        else:
            marks["drain_s"] = now - t1
            break                       # nothing left that could finish
    by_rid = {r.rid: r for r in rs if r.rid >= 0}
    for rid, rec in eng.finished().items():
        if rid in by_rid:
            by_rid[rid].queue_wait_s = rec.queue_wait_s
            by_rid[rid].finish_reason = rec.finish_reason
    return rs, marks


def _backlog(eng) -> int:
    """Requests waiting for a slot or holding one."""
    return len(eng.scheduler.waiting) + len(eng.scheduler.running)


class Tracer:
    """The device trace of a --trace 1 run: the first ``length`` seconds
    of the window, written to a temporary directory."""

    def __init__(self, length: float, log: _CallLog):
        self.length = length
        self.log = log
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.active = False

    def start(self):
        import jax

        import trace_reduce
        jax.profiler.start_trace(self.dir,
                                 profiler_options=trace_reduce.options())
        self.log.on = True
        self.active = True

    def stop(self):
        import jax
        self.log.on = False
        jax.profiler.stop_trace()
        self.active = False

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def delta(a: dict, b: dict) -> dict:
    return {k: b.get(k, 0.0) - a.get(k, 0.0) for k in b}


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def read_metrics(names: list[str], data: RunData) -> dict:
    out = {}
    for name in names:
        mod = load_module(os.path.join(HERE, "metrics", name + ".py"),
                          "metric_" + name.replace(".", "_"))
        v = mod.read(data)
        if v is not None:
            out[name] = {"value": float(v), "unit": mod.UNIT}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, hooks=None, control: bool = False,
             drain_limit_s: float = DRAIN_LIMIT_S) -> dict:
    """One run; returns the result line's dict.  For calibration and
    tests only: ``hooks(engine)`` may replace the engine's pieces before
    the run, ``control`` also judges the int8 control on the same sampled
    requests against the cell's limits (``out["control"]``), and
    ``drain_limit_s`` shortens the drain."""
    import jax

    from repro.obs import Telemetry
    import counts
    import trace_reduce

    conf = cell.conf
    ref = load_module(os.path.join(HERE, "reference",
                                   conf["reference"] + ".py"),
                      "reference_" + conf["reference"])
    dev = device_info()
    peak = counts.peaks(dev["kind"]) if dev["platform"] == "tpu" else {}
    params = ref.make_params(conf, seed)
    jax.block_until_ready(params)
    eng = _engine(cell, ref, params, Telemetry(enabled=trace))
    _check_layout(eng, params)
    if hooks is not None:
        hooks(eng)
    log = _CallLog(eng)
    warm(eng, conf["vocab_size"], seed)
    lead = cell.mix["lead_in_s"]
    reqs = traffic.generate(cell.mix, cell.spec["rate_per_s"], seed,
                            [lead, seconds, cell.mix["tail_s"]],
                            conf["vocab_size"])
    gc.collect()
    gc.freeze()
    tracer = Tracer(min(TRACE_S, seconds), log) if trace else None
    setup_s = time.perf_counter() - t_start + lead
    rs, marks = drive(eng, reqs, lead, seconds, tracer, drain_limit_s)
    gc.unfreeze()               # the engine must be collectable below
    mem = memory_peak()
    (_, c0, p0), (_, c1, p1) = marks["t0"], marks["t1"]
    phases = {k: (t - p0.get(k, (0.0, 0))[0], n - p0.get(k, (0.0, 0))[1])
              for k, (t, n) in p1.items()}
    data = RunData(widths=ref.dims(conf), peak=peak,
                   block_size=eng.cfg.block_size,
                   window=(lead, lead + seconds), reqs=rs,
                   setup_s=setup_s, counters=delta(c0, c1), phases=phases,
                   calls=log.read())
    result: dict = {}
    if trace:
        if tracer.active:
            tracer.stop()
        try:
            prof = trace_reduce.load(tracer.dir)
        finally:
            tracer.close()
        data.trace = trace_reduce.summary(prof)
        result["breakdown"] = data.trace["breakdown"]
    names = cell.per_layer if trace else cell.end_to_end
    metrics = read_metrics(names, data)

    # the reference runs once the program's state is freed
    window = data.in_window()
    records = [{"prompt": r.prompt, "tokens": r.tokens} for r in window
               if r.done]
    del eng, log
    gc.collect()
    picked = correctness.sample(records, seed)
    g = correctness.gaps(ref, params, conf, picked)
    readings = dict(
        correctness.gap_readings(g),
        unfinished=sum(not r.done for r in window),
        wrong_length=sum(r.done and (len(r.tokens) != r.max_new or
                                     r.finish_reason not in ("", "length"))
                         for r in window))
    limits = dict(cell.spec["limits"], unfinished=0, wrong_length=0)
    ok, checks = correctness.judge(readings, limits)
    device = dict(dev, memory_peak_bytes=mem)
    if trace:
        device.update(busy_s=data.trace["busy_s"],
                      window_s=data.trace["window_s"])
    late = [r.submit - r.due for r in window]
    out = {"correct": ok, "attempted": len(window),
           "failed": readings["unfinished"], "metrics": metrics,
           "device": device}
    out.update(result)
    if control:
        c = correctness.gaps(ref, params, conf, picked, quant=True)
        c_ok, c_checks = correctness.judge(
            dict(readings, **correctness.gap_readings(c)), limits)
        out["control"] = {"correct": c_ok, "checks": c_checks,
                          "logit_gaps": correctness.gap_readings(c)}
    out["load"] = {"requests_due": len(window),
                   "backlog_at_open": marks["backlog0"],
                   "backlog_at_close": marks["backlog1"],
                   "waiting_at_open": marks["waiting0"],
                   "waiting_at_close": marks["waiting1"],
                   "offered_tokens_per_s":
                   sum(r.max_new for r in window) / seconds,
                   "drain_s": marks.get("drain_s", float("nan")),
                   "tokens_compared": int(g.size),
                   "logit_gaps": correctness.gap_readings(g),
                   "requests_compared": len(picked),
                   "generator_late_p99_ms":
                   float(np.percentile(late, 99) * 1e3) if late else 0.0}
    out["checks"] = checks
    return out
