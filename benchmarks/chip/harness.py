"""One benchmark run of one cell: build, warm up, fill, measure, check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under this directory, found
by the names in ``BENCHMARK.json``:

* ``configs/<config>.json``   model numbers (as run) and serving bits,
* ``traffic/<mix>.json``      loop, lengths, rate and serving shape,
* ``metrics/<metric>.py``     one reader per per-layer metric,
* ``checks/<cell>.json``      the limit of the output comparison.

The system under test is ``repro.launch.serve_lm.LMServer`` on the
program's normal path: weights converted by ``convert_params_for_serving``
and served through ``submit``/``tick`` on the configured kernel backend.
The harness records its own spans around the calls into the executor
(``prefill``, ``decode``), so per-layer readers know each program's
shape and time without any change to the program.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import loadgen  # noqa: E402
import ref  # noqa: E402
import tails  # noqa: E402
import weights  # noqa: E402
import work  # noqa: E402

# source key -> ModelConfig field; the file's value is what runs
FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "num_hidden_layers": "n_layers", "vocab_size": "vocab",
          "rms_norm_eps": "norm_eps", "layer_norm_eps": "norm_eps",
          "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings"}
# keys whose only value the served block supports
FIXED = {"partial_rotary_factor": 1.0, "qk_layernorm": False,
         "use_parallel_residual": False, "use_qkv_bias": False,
         "hidden_act": "silu"}

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
# JAX's persistent compile cache: a fixed path inside the checkout, in a
# directory of its own. With a size limit set, JAX reads every entry's
# access-time file before it writes one, and a single entry without one
# (left by a run without the limit) makes every write fail.
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "chipbench")


class BenchError(Exception):
    """The run cannot produce a sound result."""


def prepare(chips: int) -> str:
    """Set-up shared by every entry point that runs on the chip: fail
    unless JAX sees a TPU with ``chips`` chips or more, take tile plans
    from the shape defaults (never a tuning file), and keep the compile
    cache in :data:`CACHE_DIR` through the program's
    ``use_compile_cache``. Returns the cache directory."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise BenchError(f"needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    os.environ.pop("PPAC_TILE_CACHE", None)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    from repro.core.backend import use_compile_cache
    placed = use_compile_cache()
    log(f"compile cache: {placed}")
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    return placed


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    """The workload entry ``name`` with its config entry attached."""
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            conf = next(c for c in bench["configs"] if c["name"] == w["config"])
            return {**w, "config_entry": conf, "bench": bench}
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


# -- configuration -------------------------------------------------------------

def model_config(conf: dict):
    """The program's ModelConfig for a config file: the architecture's
    published preset with every numbered key the file states."""
    from repro.configs.base import load_arch
    for k, v in FIXED.items():
        if k in conf and conf[k] != v:
            raise BenchError(f"{conf['name']}: the served block supports "
                             f"only {k}={v!r}, the file says {conf[k]!r}")
    cfg = load_arch(conf["arch"]).full()
    over = {FIELDS[k]: conf[k] for k in FIELDS if k in conf}
    over["head_dim"] = conf["hidden_size"] // conf["num_attention_heads"]
    cfg = dataclasses.replace(cfg, **over)
    s = conf["serving"]
    return dataclasses.replace(
        cfg, dtype=s["compute_dtype"], kv_dtype=s["kv_dtype"],
        ppac=dataclasses.replace(cfg.ppac, enabled=True,
                                 weight_bits=s["weight_bits"],
                                 act_bits=s["act_bits"],
                                 weight_format=s["format"],
                                 act_format=s["format"], min_features=32,
                                 backend=s["backend"]))


def model_numbers(cfg) -> Dict:
    """The numbers ``work.py`` and ``ref.py`` need, from the config as run."""
    return dict(d_model=cfg.d_model, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
                vocab=cfg.vocab, n_layers=cfg.n_layers,
                norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                tie_embeddings=cfg.tie_embeddings,
                weight_bits=cfg.ppac.weight_bits, act_bits=cfg.ppac.act_bits)


def cell_model(cell: dict):
    """(config file, traffic file, ModelConfig, model numbers) of a cell.
    A sequence may not run past the model's stated context."""
    conf = load_json(ROOT, cell["config_entry"]["file"])
    spec = loadgen.load_traffic(cell["traffic"])
    ctx = conf.get("max_position_embeddings")
    if ctx is not None and spec["max_seq"] > ctx:
        raise BenchError(f"{cell['name']}: max_seq {spec['max_seq']} is "
                         f"past the model's context of {ctx}")
    cfg = model_config(conf)
    return conf, spec, cfg, model_numbers(cfg)


# -- instrumentation ----------------------------------------------------------

class CompileCounter:
    """Backend compiles seen by JAX's monitoring events."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.count += 1
            self.seconds += duration


@dataclasses.dataclass
class Call:
    kind: str            # prefill | decode
    t0: float
    t1: float
    useful: float        # useful model operations


class Recorder:
    """Wraps the executor's ``prefill``/``decode`` entry points: a host
    span of the harness's own around each call (also written into a
    profiler trace as ``bench_prefill``/``bench_decode``), with the
    useful operations of the work."""

    def __init__(self, server, m: Dict):
        self.calls: List[Call] = []
        self.m = m
        ex = server.ex
        self._prefill, self._decode = ex.prefill, ex.decode
        ex.prefill, ex.decode = self.prefill, self.decode
        self.server = server

    def prefill(self, toks, lens, key):
        lens_np = np.asarray(lens)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_prefill"):
            out = self._prefill(toks, lens, key)
        t1 = time.perf_counter()
        # the server pads a batch with length-1 rows; every generated
        # prompt is longer (loadgen holds prompts to 2 tokens or more)
        real = lens_np[lens_np > 1]
        self.calls.append(Call("prefill", t0, t1,
                               work.prefill_useful_ops(self.m, real)))
        return out

    def decode(self, toks, cache, key):
        ctx = [len(r.prompt) + len(r.out) for r in self.server.live
               if r is not None]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_decode"):
            nxt, cache = self._decode(toks, cache, key)
            nxt = np.asarray(nxt)
        t1 = time.perf_counter()
        self.calls.append(Call("decode", t0, t1,
                               work.decode_useful_ops(self.m, ctx)))
        return nxt, cache

    def between(self, kind: str, t0: float, t1: float) -> List[Call]:
        return [c for c in self.calls
                if c.kind == kind and t0 <= c.t0 and c.t1 <= t1]


# -- the run ------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    drain_end: Optional[float] = None
    compiles: int = 0
    compile_s: float = 0.0
    lateness: Optional[dict] = None


class Run:
    """Build the cell's server from ``seed``, fill it, measure a window.

    ``server_hook`` (tests only) may replace the executor's entry points
    after the server is built, to plant a fault under the timed path."""

    def __init__(self, cell: dict, seed: int, *, t_start: float,
                 server_hook: Optional[Callable] = None):
        self.cell, self.seed, self.t_start = cell, int(seed), t_start
        self.conf, self.spec, self.cfg, self.m = cell_model(cell)
        self.traffic = loadgen.Traffic(self.spec, self.seed, self.cfg.vocab)
        self.tracks: Dict[int, tails.Track] = {}
        self.requests: Dict[int, object] = {}
        self.ticks: List[tuple] = []     # (start, end) of every tick
        self.server_hook = server_hook
        self.compiles = CompileCounter()

    # -- set-up --------------------------------------------------------------

    def build(self):
        from repro.launch.serve_lm import LMServer
        params = weights.served_params(self.cfg, self.seed)
        sp = self.spec
        self.server = LMServer(
            self.cfg, params, mode="serve", slots=sp["slots"],
            max_seq=sp["max_seq"], prefill_buckets=tuple(sp["prefill_buckets"]),
            admit_buckets=tuple(sp["admit_buckets"]))
        self.rec = Recorder(self.server, self.m)
        if self.server_hook is not None:
            self.server_hook(self.server)
        self._prefills = self.server.metrics.counter("lm_prefill_batches")

    def warm_up(self):
        """Run every program the window can use once: each prefill
        (batch bucket x length bucket) with its slot copy, and a decode
        step over all slots, on a throwaway state."""
        server, sp = self.server, self.spec
        ex = server.ex
        key = jax.random.PRNGKey(0)
        for blen in sp["admit_buckets"]:
            for plen in sp["prefill_buckets"]:
                toks = jnp.ones((blen, plen), jnp.int32)
                lens = jnp.full((blen,), plen, jnp.int32)
                _, handle = ex.prefill(toks, lens, key)
                server.cache = ex.write_slot(server.cache, handle, 0, 0)
        toks = jnp.zeros((server.slots, 1), jnp.int32)
        _, server.cache = ex.decode(toks, server.cache, key)
        jax.block_until_ready(server.cache)
        self.rec.calls.clear()

    # -- driving -------------------------------------------------------------

    def submit(self, item: loadgen.Item, now: float):
        from repro.launch.serve_lm import Request
        r = Request(item.idx, item.prompt, item.max_new)
        self.requests[item.idx] = r
        self.tracks[item.idx] = tails.Track(item.idx, sent=now)
        self.server.submit(r)
        return r

    def tick(self) -> float:
        server = self.server
        t_in = time.perf_counter()
        done = server.tick()
        t = time.perf_counter()
        self.ticks.append((t_in, t))
        c = self._prefills.value
        for r in [x for x in server.live if x is not None] + done:
            tr = self.tracks[r.rid]
            n = len(r.out) - len(tr.tokens)
            if n > 0:
                tr.add(n, t, c)
        for r in done:
            self.tracks[r.rid].outcome = r.outcome
        while server.terminal:
            r = server.terminal.pop()
            self.tracks[r.rid].outcome = r.outcome
        return t

    def fill(self, seconds: float):
        """The cell's starting state: every slot occupied (closed loop),
        or the lead-in of the arrival schedule served (open loop; the
        schedule spans the lead-in and the ``seconds`` of the window)."""
        loop = self.spec["loop"]
        if loop == "closed_backlog":
            now = time.perf_counter()
            for it in self.traffic.starting_occupants():
                self.submit(it, now)
            self._backlog = self.traffic.backlog(self.spec["slots"])
            self._top_up()
            while any(r is None for r in self.server.live):
                self.tick()
                self._top_up()
        elif loop == "open_poisson":
            self._pending = list(self.traffic.schedule(seconds))
            self._t_ref = time.perf_counter() + float(self.spec["lead_in_s"])
            self._sent, self._due = [], []
            self._drive(self._t_ref)
        else:
            raise BenchError(f"unknown loop {loop!r}")

    def _top_up(self):
        now = time.perf_counter()
        while len(self.server.queue) < self.spec["slots"]:
            self.submit(next(self._backlog), now)

    def _arrivals(self, now: float):
        """Submit every scheduled request due by ``now``."""
        while self._pending and self._t_ref + self._pending[0].due_s <= now:
            it = self._pending.pop(0)
            self.submit(it, now)
            due = self._t_ref + it.due_s
            self.tracks[it.idx].due = due
            self._sent.append(now)
            self._due.append(due)

    def _drive(self, end: float) -> float:
        """Tick until a tick returns at or after ``end``; an open loop
        submits arrivals as they fall due and sleeps while idle."""
        closed = self.spec["loop"] == "closed_backlog"
        t = time.perf_counter()
        while t < end:
            if closed:
                self._top_up()
            else:
                now = time.perf_counter()
                self._arrivals(now)
                if not self.server.queue and all(
                        r is None for r in self.server.live):
                    nxt = (self._t_ref + self._pending[0].due_s
                           if self._pending else end)
                    time.sleep(max(0.0, min(nxt, end) - now))
                    t = time.perf_counter()
                    continue
            t = self.tick()
        return t

    def backlog_at(self, t: float) -> int:
        """Open loop: requests due by ``t`` that had no first token by
        ``t``, the ones the generator has not yet submitted included."""
        n = sum(1 for it in self._pending if self._t_ref + it.due_s <= t)
        for tr in self.tracks.values():
            if tr.due is not None and tr.due <= t and (
                    not tr.tokens or tr.tokens[0][0] > t):
                n += 1
        return n

    def measure(self, seconds: float, *, trace_dir: Optional[str] = None
                ) -> Window:
        """Drive the server for ``seconds``: the window closes at the
        return of the first tick that ends at or after its length, so it
        holds whole ticks and its length is read, not assumed. An open
        loop's window is the schedule's: it opens at the time the
        lead-in ends, even when the tick that crossed it returned later,
        so it sees every arrival the schedule puts in it."""
        c0, s0 = self.compiles.count, self.compiles.seconds
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # set-up's garbage is collected before the window, not in it
        gc.collect()
        gc.freeze()
        span = jax.profiler.TraceAnnotation("bench_window")
        span.__enter__()
        t0 = time.perf_counter()
        if self.spec["loop"] == "open_poisson":
            t0 = self._t_ref
        t1 = self._drive(t0 + seconds)
        span.__exit__(None, None, None)
        gc.unfreeze()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        win = self.win = Window(t0, t1, compiles=self.compiles.count - c0,
                                compile_s=self.compiles.seconds - s0)
        if self.spec["loop"] == "open_poisson":
            # what fell due while the last tick ran is submitted now, so
            # the drain serves it or counts it missing
            self._arrivals(time.perf_counter())
            win.lateness = loadgen.lateness_report(self._due, self._sent)
            win.drain_end = self._drain(t0, t1)
        return win

    def _drain(self, t0: float, t1: float) -> float:
        """Tick on, with no new arrivals, until every request due in the
        window has its first token, for at most ``drain_s``."""
        limit = time.perf_counter() + float(self.spec["drain_s"])
        t = time.perf_counter()
        while t < limit:
            waiting = [tr for tr in self.tracks.values()
                       if tr.due is not None and t0 <= tr.due < t1
                       and not tr.tokens and tr.outcome is None]
            if not waiting:
                break
            t = self.tick()
        return t

    # -- results -------------------------------------------------------------

    def end_to_end(self, win: Window) -> Dict[str, float]:
        return tails.window_metrics(list(self.tracks.values()), win.t0,
                                    win.t1, drain_end=win.drain_end)

    def slowest_ticks(self, win: Window, top: int = 5) -> List[Dict]:
        """The window's ``top`` longest ticks: their length, and the time
        spent in the executor's prefill and decode calls inside them (the
        rest is host work of the server and the harness)."""
        out = []
        for a, b in self.ticks:
            if win.t0 <= a and b <= win.t1:
                calls = self.rec.between("prefill", a, b)
                dec = self.rec.between("decode", a, b)
                out.append({"s": b - a, "prefills": len(calls),
                            "prefill_s": sum(c.t1 - c.t0 for c in calls),
                            "decode_s": sum(c.t1 - c.t0 for c in dec)})
        return sorted(out, key=lambda x: -x["s"])[:top]

    def compared(self) -> List[Dict]:
        """The requests the output comparison reads, drawn from the seed:
        ``check_requests`` finished ones, the longest always among them,
        and ``check_admitted`` of those admitted inside the window (whose
        prefill and decode steps the window timed), finished or not, the
        one with the most served tokens always among them. Each is a
        record of its prompt, its served tokens and its groups."""
        fin = [r for r in self.requests.values() if r.outcome == "completed"]
        adm = [r for r in self.requests.values()
               if self.tracks[r.rid].tokens
               and self.win.t0 < self.tracks[r.rid].tokens[0][0] <= self.win.t1]
        recs: Dict[int, Dict] = {}
        for group, reqs, k, stream in (
                ("finished", fin, self.spec["check_requests"], 99),
                ("window", adm, self.spec["check_admitted"], 98)):
            for r in sample(reqs, int(k), self.seed, stream):
                rec = recs.setdefault(r.rid, {
                    "rid": r.rid, "prompt": [int(t) for t in r.prompt],
                    "served": [int(t) for t in r.out], "groups": [],
                    "short": r.outcome == "completed"
                    and len(r.out) != r.max_new})
                rec["groups"].append(group)
        return list(recs.values())

    def free(self):
        """Drop the program's state, so the reference runs in the room."""
        for name in ("server", "rec"):
            if hasattr(self, name):
                delattr(self, name)
        gc.collect()


# -- the output comparison -----------------------------------------------------

def check_limit(cell: dict) -> dict:
    """The cell's limits: ``checks/<cell>.json``."""
    return cell.get("limits") or load_json(HERE, "checks",
                                           f"{cell['name']}.json")


def sample(reqs: List[object], k: int, seed: int, stream: int
           ) -> List[object]:
    """``k`` requests drawn from the seed, the one with the most served
    tokens always among them."""
    if not reqs or k < 1:
        return []
    reqs = sorted(reqs, key=lambda r: r.rid)
    longest = max(reqs, key=lambda r: len(r.out))
    rest = [r for r in reqs if r is not longest]
    rng = loadgen.rng_for(seed, stream)
    pick = list(rng.permutation(len(rest))[:k - 1])
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(records: List[Dict], cfg, m: Dict, seed: int,
                   length: int, *, ctrl_bits: int = 0) -> List[Dict]:
    """Run the plain reference once over each record's prompt and served
    tokens (the program's state must already be freed) and set its
    ``gap``: the widest gap between the reference's best logit and its
    logit for a served token. With ``ctrl_bits`` also its ``ctrl``: the
    same gap for the token that the reference at ``ctrl_bits``-bit
    activations puts first at each of those positions (the control)."""
    params = weights.float_params(cfg, seed)
    for rec in records:
        gap, ctrl, n = ref.served_gaps(params, m, rec["prompt"],
                                       rec["served"], length=length,
                                       ctrl_bits=ctrl_bits)
        rec["gap"], rec["tokens"] = gap, n
        if ctrl_bits:
            rec["ctrl"] = ctrl
    del params
    gc.collect()
    return records


def judge(records: List[Dict], limits: Dict, *, key: str = "gap"):
    """The numbers compared, each beside its limit, and whether every one
    holds. ``key`` picks whose tokens are judged: ``gap`` the program's,
    ``ctrl`` the control's. A group with nothing compared reads None and
    fails."""
    def widest(group):
        vals = [r[key] for r in records if group in r["groups"]]
        return max(vals) if vals else None
    lim = limits["max_logit_gap"]
    checks = {
        "max_logit_gap": {"value": widest("finished"), "limit": lim},
        "window_logit_gap": {"value": widest("window"), "limit": lim},
        "short_requests": {"value": sum(1 for r in records if r["short"]),
                           "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return checks, correct


# -- one whole run ---------------------------------------------------------------

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def execute(cell: dict, seed: int, seconds: float, trace: bool, *,
            t_start: float, server_hook: Optional[Callable] = None,
            keep_trace: Optional[str] = None, keep: Optional[str] = None
            ) -> dict:
    """Build, warm up, fill, measure and check one cell; returns the
    result line's object. ``keep`` names a file for the compared
    requests, which ``control.py`` reads."""
    name = cell["name"]
    bench = cell["bench"]
    run = Run(cell, seed, t_start=t_start, server_hook=server_hook)
    run.build()
    run.warm_up()
    run.fill(seconds)
    setup_s = time.perf_counter() - t_start
    log(f"set-up compiles: {run.compiles.count} "
        f"({run.compiles.seconds:.3f} s)")
    tdir = None
    if trace:
        import tempfile
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    win = run.measure(seconds, trace_dir=tdir)
    if keep_trace is not None and tdir is not None:
        import shutil
        shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
    e2e = run.end_to_end(win)
    e2e["setup_s"] = setup_s
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    log(f"window: {win.t1 - win.t0:.4f} s, compiles inside it: "
        f"{win.compiles} ({win.compile_s:.3f} s); set-up {setup_s:.3f} s")
    if win.lateness is not None:
        log(f"generator lateness: {json.dumps(win.lateness)}")
    log("end to end: " + json.dumps(e2e))
    log("slowest ticks: " + json.dumps(run.slowest_ticks(win)))

    metrics, breakdown = {}, None
    if trace:
        import trace_reduce
        ctx = trace_reduce.Context(run, win, tdir, device["kind"])
        device["busy_s"], device["window_s"] = ctx.busy_s, ctx.window_s
        breakdown = ctx.breakdown()
        for m in bench["per_layer"]:
            if applies(m, name):
                v = load_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        for m in bench["end_to_end"]:
            if applies(m, name):
                if m["name"] not in e2e:
                    raise BenchError(f"{m['name']} has nothing to read")
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    attempted = sum(1 for tr in run.tracks.values()
                    if tr.sent is not None and tr.sent <= win.t1
                    and (not tr.tokens or tr.tokens[-1][0] >= win.t0))
    failed = sum(1 for tr in run.tracks.values()
                 if tr.outcome in ("failed", "shed")) + \
        int(e2e.get("n_missing", 0))
    run.free()
    recs = reference_gaps(run.compared(), run.cfg, run.m, run.seed,
                          run.spec["max_seq"])
    checks, correct = judge(recs, check_limit(cell))
    if keep is not None:
        with open(keep, "w") as f:
            json.dump({"workload": name, "seed": run.seed, "records": recs},
                      f)
    log(f"compared {sum(r['tokens'] for r in recs)} served tokens of "
        f"{len(recs)} requests with the reference: "
        f"{sum('finished' in r['groups'] for r in recs)} finished, "
        f"{sum('window' in r['groups'] for r in recs)} admitted in the "
        f"window")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
