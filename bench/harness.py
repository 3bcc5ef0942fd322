"""One run of one benchmark cell: set-up, an open-loop window on the wall
clock through the serving program's continuous path, and the check of
what that window produced against the configuration's plain reference.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file found by name (see ``Cell``); this module is the
general machinery and changes for none of them.  The program is driven
only through its public entry points: ``ContinuousRuntime.run`` with a
wall-clock arrival source, an ``EngineContinuousExecutor`` over a
``ServingEngine`` and a ``KVArena``.  Timestamps come from thin
subclasses that time the public calls (``step``, ``start_chunked``,
``refill_chunked``, ``poll_chunked``) and change nothing they do.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import trace as trace_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class UnknownDevice(RuntimeError):
    """The device kind has no entry in ``peaks.json``."""


class WindowClosed(Exception):
    """Raised from the executor's step once the window and its drain are
    over: the way out of ``ContinuousRuntime.run``'s open-ended loop."""


# ---------------------------------------------------------------------------
# The cell, assembled from files found by name
# ---------------------------------------------------------------------------


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names:
    the configuration file and its reference module
    (``<config dir>/<reference>.py``), the traffic mix
    (``bench/traffic/<traffic>.json``), the cell's load and limits
    (``bench/cells/<workload>.json``) and its metrics, each with a
    reader ``bench/metrics/<metric>.py``."""
    name: str
    root: Path
    workload: dict
    config_entry: dict
    config: dict
    traffic: dict
    load: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def reference(self):
        path = (self.root / self.config_entry["file"]).parent \
            / f"{self.config['reference']}.py"
        return _load_module(path, f"bench_ref_{self.config['reference']}")

    def reader(self, metric: str):
        return _load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                            f"bench_metric_{metric.replace('.', '_')}")


def _applies(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = _load_json(root / "BENCHMARK.json")
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    centry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(name=workload, root=root, workload=wl, config_entry=centry,
                config=_load_json(root / centry["file"]),
                traffic=_load_json(root / "bench" / "traffic"
                                   / f"{wl['traffic']}.json"),
                load=_load_json(root / "bench" / "cells"
                                / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found {info['count']} "
                     f"{info['platform']} device(s) ({info['kind']})")
    if info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{info['count']}")
    return info


def peaks_for(kind: str, path: Path = BENCH / "peaks.json") -> dict:
    table = _load_json(path)
    if kind not in table:
        have = sorted(k for k in table if k != "source")
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {path}; "
                            f"have {have}")
    return table[kind]


# ---------------------------------------------------------------------------
# What one run records
# ---------------------------------------------------------------------------


@dataclass
class Req:
    rid: int
    due: float
    s: int
    released: Optional[float] = None
    admitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    tokens: int = 0
    finished: bool = False
    t0: Optional[int] = None        # cohort step at admission


@dataclass
class Step:
    start: float
    end: float
    busy: bool                       # the executor held work
    occupancy: float
    pages_in_use: float              # share of the arena's pages


@dataclass
class Recorder:
    """Wall-clock record of one window.  Times are seconds from the
    window's start on the host's monotonic clock."""
    clock0: float = 0.0
    active: bool = False
    reqs: Dict[int, Req] = field(default_factory=dict)
    steps: List[Step] = field(default_factory=list)
    # (time, tokens that reached the host, decode iterations, useful FLOPs)
    polls: List[tuple] = field(default_factory=list)
    # (time, useful FLOPs) of each prefill call
    prefills: List[tuple] = field(default_factory=list)
    compiles_in_window: int = 0

    def now(self) -> float:
        return time.perf_counter() - self.clock0


class _Span:
    """A named host span in the profiler's trace (no-op when off)."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)


def _bench_classes():
    """The timing subclasses, built on first use so that importing this
    module does not import the program."""
    import jax.numpy as jnp
    from repro.serving.engine import ServingEngine
    from repro.serving.runtime import EngineContinuousExecutor

    class BenchEngine(ServingEngine):
        """A ``ServingEngine`` whose prompts come from the frozen schedule
        and whose public chunked calls are timed."""

        def bench_bind(self, rec: Recorder, prompts: Dict[int, list],
                       dims: dict, span: _Span, kv_layers: tuple):
            from bench import flops
            self._rec, self._prompts, self._span = rec, prompts, span
            self._kv_layers = kv_layers
            self.kv: Dict[int, dict] = {}     # rid -> its K/V on the host
            self._kv_pending: Dict[int, dict] = {}
            self._by_id = {id(p): rid for rid, p in prompts.items()}
            self._slot_rid: Dict[int, int] = {}
            self._seen = np.zeros(self.batch_capacity, np.int64)
            self._t_prev = 0
            self._flops = flops
            self._dims = dims

        def synth_prompts(self, requests, rng):
            return ([self._prompts[r.rid] for r in requests],
                    [min(r.n, self.n_max) for r in requests])

        def _land(self, slots, prompts, t0):
            rec = self._rec
            fl = 0.0
            for slot, p in zip(slots, prompts):
                rid = self._by_id.get(id(p))
                self._slot_rid[slot] = rid
                self._seen[slot] = 0
                if rid is not None and rid in rec.reqs:
                    rec.reqs[rid].t0 = int(t0)
                    fl += self._flops.prefill_flops(self._dims, len(p))
            if rec.active:
                rec.prefills.append((rec.now(), fl))

        def start_chunked(self, prompts, *a, **kw):
            with self._span("prefill"):
                st = super().start_chunked(prompts, *a, **kw)
            self._slot_rid = {}
            self._t_prev = 0
            self._land(range(len(prompts)), prompts, 0)
            return st

        def refill_chunked(self, state, slots, prompts, n_tokens, t_now,
                           *a, **kw):
            with self._span("prefill"):
                st = super().refill_chunked(state, slots, prompts, n_tokens,
                                            t_now, *a, **kw)
            if st is not state:
                self._land(slots, prompts, t_now)
            return st

        def kv_of_row(self, state, slot: int) -> dict:
            """The row's keys and values at the compared layers, as its
            pages hold them, by cache position: a device gather, on its
            way to the host when this returns."""
            got = _row_kv()(state.arena.buffers(),
                            jnp.asarray(state.table.host[slot]),
                            layers=self._kv_layers, dh=self._dims["dh"])
            for a in got.values():
                a.copy_to_host_async()
            return got

        def release_slots(self, state, slots):
            # a finished row's keys and values, read before its pages
            # go back to the arena: what the check compares
            for rid, got in self._kv_pending.items():
                self.kv[rid] = {n: np.asarray(a) for n, a in got.items()}
            self._kv_pending = {}
            for slot in slots:
                rid = self._slot_rid.get(slot)
                if rid in self._rec.reqs and rid not in self.kv and \
                        state.table.row_leases(slot):
                    self._kv_pending[rid] = self.kv_of_row(state, slot)
            return super().release_slots(state, slots)

        def kv_collect(self) -> Dict[int, dict]:
            for rid, got in self._kv_pending.items():
                self.kv[rid] = {n: np.asarray(a) for n, a in got.items()}
            self._kv_pending = {}
            return self.kv

        def generate_chunked(self, state, k):
            with self._span("segment"):
                return super().generate_chunked(state, k)

        def poll_chunked(self, state, with_tokens=True):
            with self._span("poll"):
                got = super().poll_chunked(state, with_tokens)
            rec = self._rec
            t = rec.now()
            _, lengths, _, step_t = got
            tokens, fl = 0, 0.0
            for slot, rid in self._slot_rid.items():
                new = int(lengths[slot]) - int(self._seen[slot])
                if new <= 0 or rid is None:
                    continue
                r = rec.reqs.get(rid)
                if r is not None:
                    if r.first is None:
                        r.first = t
                    fl += self._flops.decode_flops(
                        self._dims, r.s, int(self._seen[slot]), new)
                self._seen[slot] = lengths[slot]
                tokens += new
            iters = step_t - self._t_prev
            self._t_prev = step_t
            if rec.active:
                rec.polls.append((t, tokens, iters, fl))
            return got

    class BenchExecutor(EngineContinuousExecutor):
        """An ``EngineContinuousExecutor`` whose ``step`` is timed, and
        which ends the run once the window and its drain are over."""

        def bench_bind(self, rec: Recorder, adapter, drain_s: float,
                       span: _Span, on_window_end):
            self._rec, self._adapter = rec, adapter
            self._drain_s, self._span = drain_s, span
            self._on_window_end = on_window_end

        def step(self, env, k):
            rec, ad = self._rec, self._adapter
            start = rec.now()
            if start >= ad.seconds:
                self._on_window_end()
                if self.idle() or start >= ad.seconds + self._drain_s:
                    raise WindowClosed
            busy = not self.idle()
            for r in self.resident(None):
                q = rec.reqs.get(r.rid)
                if q is not None and q.admitted is None:
                    q.admitted = start
            if not busy:
                nxt = ad.next_due()
                wait = min(ad.seconds if nxt is None else nxt,
                           ad.seconds) - start
                if wait > 0:
                    with self._span("wait"):
                        time.sleep(wait)
            with self._span("step"):
                finished, occ = super().step(env, k)
            end = rec.now()
            arena = self.arena
            rec.steps.append(Step(
                start, end, busy, occ,
                1.0 - arena.free_pages / arena.total_pages))
            for _, r, tokens in finished:
                q = rec.reqs.get(r.rid)
                if q is not None:
                    q.finished, q.last, q.tokens = True, end, int(tokens)
            return finished, occ

    return BenchEngine, BenchExecutor


@functools.lru_cache(maxsize=None)
def _row_kv():
    """The jitted read of one table row's K and V at ``layers``:
    ``(len(layers), n_blocks * block_tokens, heads, dh)`` each, in cache
    positions."""
    import jax
    import jax.numpy as jnp

    def gather(bufs, ids, layers, dh):
        out = {}
        for n in ("k", "v"):
            # one gather of (layer, page) pairs: no layer is copied
            g = bufs[n][jnp.asarray(layers)[:, None], ids[None, :]]
            g = g[..., :dh]
            out[n] = g.reshape((len(layers), -1) + g.shape[3:])
        return out

    return jax.jit(gather, static_argnames=("layers", "dh"))


class WallClockArrivals:
    """The runtime's arrival source (any object with ``within``): releases
    each request of the frozen schedule at the first boundary after its
    due time has passed on the wall clock, stamped with the runtime's own
    segment start so that the program's deadline logic stays consistent
    on the program's clock, and releases nothing due after the window."""

    def __init__(self, sched, rec: Recorder, seconds: float):
        from repro.core.request import Request
        self._req = Request
        self.sched = sched
        self.rec = rec
        self.seconds = seconds
        self.i = 0

    def next_due(self) -> Optional[float]:
        return self.sched[self.i].due_s if self.i < len(self.sched) \
            else None

    def within(self, t0: float, t1: float) -> list:
        now = self.rec.now()
        out = []
        while self.i < len(self.sched) and \
                self.sched[self.i].due_s <= min(now, self.seconds):
            d = self.sched[self.i]
            self.i += 1
            self.rec.reqs[d.rid].released = now
            out.append(self._req(rid=d.rid, s=d.s, n=d.n, tau=d.tau, a=d.a,
                                 h=d.h, arrival=t0))
        return out


# ---------------------------------------------------------------------------
# Set-up: configuration, weights, engine, warm-up
# ---------------------------------------------------------------------------


def model_config(cell: Cell, dims: dict):
    """The program's ``ModelConfig`` for the configuration file's sizes."""
    from repro.config import ModelConfig
    c = cell.config
    if c.get("model_type") != "bloom":
        raise ValueError(f"no program mapping for model_type "
                         f"{c.get('model_type')!r}")
    return ModelConfig(
        arch_id=c["program_arch"], family="dense", n_layers=dims["L"],
        d_model=dims["D"], n_heads=dims["H"], n_kv_heads=dims["H"],
        d_head=dims["dh"], d_ff=dims["F"], vocab=dims["V"],
        norm="layernorm", act="gelu", tie_embeddings=True,
        rope_theta=dims["theta"], dtype="bfloat16", source=c["source"])


CONTROLS = ("w8a8", "w4a16")


def program_params(raw: dict, dims: dict, control: Optional[str] = None):
    """The serving program's parameter tree around the seeded weights:
    int8 ``QTensor`` matrices, a bfloat16 tied embedding, unit norms.

    A ``control`` serves the next precision down instead, through the
    program's own tier for it: ``"w8a8"`` tags the int8 matrices for
    int8 activations, ``"w4a16"`` requantizes them to packed int4."""
    import jax.numpy as jnp
    from repro.quant.ptq import QTensor
    if control not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {control!r}; have {CONTROLS}")
    L, D = dims["L"], dims["D"]
    layers = _int4(raw["layers"]) if control == "w4a16" else raw["layers"]
    bits = 4 if control == "w4a16" else 8
    act_bits = 8 if control == "w8a8" else 16

    def qt(name):
        q, s = layers[name]
        K, N = raw["layers"][name][0].shape[1:]
        return QTensor(q=q, scale=s, bits=bits, shape=(L, K, N),
                       dtype=jnp.bfloat16, act_bits=act_bits)

    ones = jnp.ones((L, D), jnp.bfloat16)
    return {"embed": raw["embed"],
            "layers": {"attn": {n: qt(n) for n in ("wq", "wk", "wv", "wo")},
                       "norm1": ones, "norm2": ones,
                       "ffn": {"w1": qt("w1"), "w2": qt("w2")}},
            "final_norm": jnp.ones((D,), jnp.bfloat16)}


def _int4(layers: dict) -> dict:
    """The int8 matrices requantized per output column to int4 (absmax
    / 7) and packed two rows to a byte along K, row 2i in the low
    nibble: the layout of the program's W4A16 tier.  One layer at a
    time, on the device."""
    import jax
    import jax.numpy as jnp

    def one(q, s):
        w = q.astype(jnp.float32) * s
        absmax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
        s4 = jnp.where(absmax > 0, absmax / 7.0, 1.0)
        q4 = jnp.clip(jnp.round(w / s4), -8, 7).astype(jnp.int32)
        packed = (q4[0::2] & 0xF) | ((q4[1::2] & 0xF) << 4)
        return packed.astype(jnp.uint8).astype(jnp.int8), s4

    @jax.jit
    def all_layers(layers):
        return {n: jax.lax.map(lambda qs: one(*qs), qs)
                for n, qs in layers.items()}

    return all_layers(layers)


def warm_up(engine, arena, k: int) -> None:
    """Compile and run once every program the window drives: a cohort
    start (prefill + page scatter), a decode segment, the read of a
    row's keys and values, the poll, a refill into a live cohort and the
    release of its pages."""
    B = engine.batch_capacity
    prompts = [[1 + i] * 4 for i in range(B)]
    st = engine.start_chunked(prompts, [engine.n_max] * B, arena=arena)
    st = engine.generate_chunked(st, k)
    for a in engine.kv_of_row(st, 0).values():
        np.asarray(a)
    _, _, _, t = engine.poll_chunked(st, with_tokens=True)
    st = engine.release_slots(st, [0])
    st = engine.refill_chunked(st, [0], [[7] * 4], [1], t_now=t)
    st = engine.generate_chunked(st, k)
    engine.poll_chunked(st, with_tokens=True)
    engine.release_all(st)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


@dataclass
class RunResult:
    """What the metric readers see."""
    cell: Cell
    seconds: float
    setup_s: float
    rec: Recorder
    dims: dict
    serving: dict
    peaks: dict
    n_devices: int
    trace: object = None              # bench.trace.Trace, when traced
    trace_window: Optional[tuple] = None   # (lo, hi) on the trace clock

    def due_in_window(self) -> List[Req]:
        return [r for r in self.rec.reqs.values() if r.due < self.seconds]

    def served(self) -> List[Req]:
        return [r for r in self.due_in_window()
                if r.finished and r.first is not None]

    def window_steps(self) -> List[Step]:
        return [s for s in self.rec.steps if s.start < self.seconds]


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation; None when
    empty."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def _sample(served: List[Req], outputs: dict, seed: int, n: int
            ) -> List[Req]:
    """The requests the reference checks: the longest, the one admitted
    deepest into a live cohort, and others drawn from the seed."""
    pool = [r for r in served if r.rid in outputs and len(outputs[r.rid])]
    if not pool:
        return []
    pick = [max(pool, key=lambda r: (len(outputs[r.rid]), -r.rid))]
    deep = max(pool, key=lambda r: (r.t0 or 0, -r.rid))
    if (deep.t0 or 0) > 0 and deep is not pick[0]:
        pick.append(deep)
    rest = [r for r in pool if r not in pick]
    rng = np.random.default_rng([seed, 1])
    for i in rng.permutation(len(rest))[:max(0, n - len(pick))]:
        pick.append(rest[int(i)])
    return pick


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_tpu: bool = True,
             clock_start: Optional[float] = None,
             control: Optional[str] = None, rate: Optional[float] = None,
             peaks: Optional[dict] = None, tamper=None, tamper_kv=None,
             inspect=None,
             log=lambda *a: print(*a, file=sys.stderr, flush=True)
             ) -> dict:
    """Run one cell once and return the result line's object.

    ``control`` (``"w8a8"`` or ``"w4a16"``) serves the program's tier of
    the next precision down in place of the configuration's: the
    precision control, which the check must fail.  ``rate`` offers
    another load than the cell's (the knee sweep).  Hooks for tests:
    ``tamper(outputs)`` may alter the served tokens and
    ``tamper_kv(kv)`` the rows' keys and values before the check, and
    ``inspect(result, outputs, prompts)`` sees what the check will."""
    t_proc = time.perf_counter() if clock_start is None else clock_start
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    cell = load_cell(workload, root)
    device = device_info(cell.chips, require_tpu)
    peaks = peaks if peaks is not None else peaks_for(device["kind"])

    import jax
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    cache = None
    if device["platform"] == "tpu":
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.core.environment import tpu_env
    from repro.serving.kv_arena import KVArena
    from repro.serving.runtime import ContinuousRuntime
    from bench import traffic as traffic_mod

    ref = cell.reference()
    dims = ref.dims(cell.config)
    sv = cell.config["serving"]
    cfg = model_config(cell, dims)
    rec = Recorder()

    def on_compile(event, duration, **_):
        if rec.active and \
                event == "/jax/core/compile/backend_compile_duration":
            rec.compiles_in_window += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    BenchEngine, BenchExecutor = _bench_classes()
    raw = ref.make_weights(seed, dims)
    params = program_params(raw, dims, control)
    del raw
    engine = BenchEngine(cfg, params=params,
                         batch_capacity=sv["batch_capacity"],
                         s_max=sv["s_max"], n_max=sv["n_max"])
    del params
    arena = KVArena.for_engines(engine, block_tokens=sv["block_tokens"])
    sched = traffic_mod.schedule(
        cell.traffic, cell.load["rate_per_s"] if rate is None else rate,
        seconds, seed, dims["V"])
    prompts = {d.rid: d.prompt.tolist() for d in sched}
    span = _Span(trace)
    engine.bench_bind(rec, prompts, dims, span, ref.kv_layers(dims))
    warm_up(engine, arena, sv["k"])
    for d in sched:
        rec.reqs[d.rid] = Req(rid=d.rid, due=d.due_s, s=d.s)
    adapter = WallClockArrivals(sched, rec, seconds)
    ex = BenchExecutor(engine, seed=seed, arena=arena, collect_tokens=True)
    env = tpu_env(cfg.arch_id, cell.config["precision"],
                  chips=cell.chips).with_(model=cfg)
    runtime = ContinuousRuntime(env, sv["policy"], ex, k=sv["k"])

    tdir = root / TRACE_DIR
    tracing = []                        # the open "window" span, if any

    def window_end():
        if tracing:
            tracing.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()
        rec.active = False

    ex.bench_bind(rec, adapter, cell.load["drain_s"], span, window_end)
    setup_s = time.perf_counter() - t_proc
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        tracing.append(span("window"))
        tracing[0].__enter__()
    rec.clock0 = time.perf_counter()
    rec.active = True
    try:
        runtime.run(gen=adapter, n_epochs=10 ** 9, warmup_epochs=0)
    except WindowClosed:
        pass
    window_end()
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = mem.get("peak_bytes_in_use")
    outputs = dict(ex.outputs)
    kv = engine.kv_collect()
    if tamper is not None:
        tamper(outputs)
    if tamper_kv is not None:
        tamper_kv(kv)

    result = RunResult(cell=cell, seconds=seconds, setup_s=setup_s, rec=rec,
                       dims=dims, serving=sv, peaks=peaks,
                       n_devices=cell.chips)
    lateness = [r.released - r.due for r in result.due_in_window()
                if r.released is not None]
    log(f"adapter: {len(lateness)} released, lateness p50 "
        f"{percentile(lateness, 50)} s, max "
        f"{max(lateness) if lateness else None} s; compiles in window "
        f"{rec.compiles_in_window}; compile cache {cache}")
    served = result.served()
    ttft = [1e3 * (r.first - r.due) for r in served]
    log(f"served {len(served)} of {len(result.due_in_window())} due; "
        f"ttft p50 {percentile(ttft, 50)} ms p95 {percentile(ttft, 95)} "
        f"ms max {max(ttft) if ttft else None} ms")

    if trace and tdir.is_dir() and (p := trace_mod.find(str(tdir))):
        tr = trace_mod.load(p)
        result.trace = tr
        result.trace_window = trace_mod.window(tr, "window")
        if result.trace_window is not None:
            lo, hi = result.trace_window
            busy = [trace_mod.busy_s(ops, lo, hi)
                    for ops in tr.ops[:cell.chips]]
            device["busy_s"] = sum(busy) / max(1, len(busy))
            device["window_s"] = hi - lo

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"]).read(result)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": False, "attempted": len(result.due_in_window()),
            "failed": len(result.due_in_window()) - len(served),
            "metrics": metrics, "device": device}
    if result.trace is not None and result.trace_window is not None:
        line["breakdown"] = breakdown(result)

    if inspect is not None:
        inspect(result, outputs, prompts)

    # -- the check, once the program's state is freed ------------------------
    del ex, runtime, engine, arena
    gc.collect()
    checks = check(cell, ref, dims, sv, seed, served, outputs, prompts, kv)
    info = checks.pop("info", {})
    line["correct"] = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    log(f"check info {json.dumps(info)}")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    line["check_info"] = info
    line["checks"] = checks
    return line


def check(cell: Cell, ref, dims: dict, sv: dict, seed: int,
          served: List[Req], outputs: dict, prompts: dict, kv: dict
          ) -> dict:
    """The reference's reading of a seeded sample of the finished
    requests: the widest gap by which a served token's logit lies below
    the reference's best (``max_logit_gap``), and the largest relative
    error of the keys or values the program's cache held at a compared
    position, over the compared layers (``kv_rel_err``) and at the first
    (``kv_rel_err_first``).  Each number the cell's load file gives a
    limit is compared; the others are printed beside them."""
    sample = _sample([r for r in served if r.rid in kv], outputs, seed,
                     cell.load["check_requests"])
    if not sample:
        return {}
    weights = ref.make_weights(seed, dims)
    gaps, errs, n_tok = [], [], 0
    B = cell.load.get("check_batch", 4)
    for i in range(0, len(sample), B):
        rows = [(prompts[r.rid], outputs[r.rid], r.t0 or 0, kv[r.rid])
                for r in sample[i:i + B]]
        g, e = ref.compare(weights, dims, sv["s_max"], sv["n_max"], rows)
        g = np.asarray(g)
        for j, (_, out, _, _) in enumerate(rows):
            gaps.extend(g[j, :len(out)].tolist())
        errs.append(np.asarray(e))
        n_tok += sum(len(o) for _, o, _, _ in rows)
    gaps = np.asarray(gaps)
    errs = np.concatenate(errs)          # (rows, layers, K/V, worst/whole)
    layers = ref.kv_layers(dims)
    got = {"max_logit_gap": float(gaps.max()),
           "kv_rel_err": float(errs[..., 0].max()),
           "kv_rel_err_first": float(errs[:, 0, :, 0].max())}
    info = {"tokens": n_tok, "requests": len(sample),
            "mean_logit_gap": float(gaps.mean()),
            "flips": int((gaps > 0).sum()),
            # per compared layer: [K, V]
            "kv_max_rel_err_by_layer": {
                str(l): errs[:, j, :, 0].max(0).tolist()
                for j, l in enumerate(layers)},
            "kv_row_rel_err_by_layer": {
                str(l): errs[:, j, :, 1].max(0).tolist()
                for j, l in enumerate(layers)}}
    out = {name: {"value": v, "limit": cell.load[name]}
           for name, v in got.items() if name in cell.load}
    out["info"] = dict(info, **{n: v for n, v in got.items()
                                if n not in cell.load})
    return out


def breakdown(result: RunResult) -> dict:
    """The device operations that took most time in the traced window,
    and its longest idle gaps by the benchmark span the host was in."""
    tr, (lo, hi) = result.trace, result.trace_window
    ops = trace_mod.within(tr.ops[0], lo, hi) if tr.ops else []
    leaf = [trace_mod.Event(trace_mod.short_name(e.name), e.start_s, e.dur_s)
            for e in ops if not trace_mod.is_container(e.name)]
    top = list(trace_mod.by_name(leaf).items())[:10]
    gaps = sorted(trace_mod.idle_gaps(ops, lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[trace_mod.host_doing(tr, (a + b) / 2,
                                                skip=("window",)), b - a]
                          for a, b in gaps]}
