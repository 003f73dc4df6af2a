"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix, cell or metric is
found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` -- the model's sizes as run, the cut, the
  serving sizes, and under ``"arch"`` the module that knows its block
  (``bench/arch/``: the program's config, the weight layout, the work
  counts and the plain reference);
* ``bench/traffic/<mix>.json``    -- parameters of the one generator;
* ``bench/cells/<cell>.json``     -- the offered rate (from the knee sweep),
  the TTFT and ITL limits, and the limits of the correctness check;
* ``bench/metrics/<metric>.py``   -- one reader per metric.

The window drives the program's normal serving path: ``Engine`` over a paged
``RealBackend`` in the configuration's dtype, with the PARS policy over the
BERT-base ranker, chunked prefill, prefix caching and re-ranking. Requests
are offered open-loop on the wall clock at the cell's rate for ``seconds``;
every one is timed from when it was due, and the loop then serves until
each request due in the window has finished (or ``DRAIN_S`` has passed).
Afterwards the program's state is freed and the plain reference checks a
sample of what the window served.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: Serve at most this long past the window for requests due in it.
DRAIN_S = 60.0
#: Length of the traced window of a traced run, in seconds: the last of
#: the measured window, or where the cell file's ``trace_at_share`` puts it
#: (see :func:`trace_start`). The profiler runs on until the loop has served
#: every request, so that writing the trace stalls no request; the
#: reduction keeps the first ``TRACE_S`` seconds of it.
TRACE_S = 3.0
#: Served tokens the correctness sample reaches for (longest request first).
SAMPLE_TOKENS = 1024
SAMPLE_MAX_REQUESTS = 8


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------- the spec
@dataclass
class Cell:
    name: str
    config: dict            # configuration file, as run
    mix: dict               # traffic parameters
    cell: dict              # rate, limits, check limits
    end_to_end: list        # BENCHMARK.json metric entries of this cell
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its configuration
    and cell files from under ``root``; the configuration's ``"arch"``
    becomes an absolute path."""
    from bench import arch
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    from bench.traffic import generator
    config = json.loads((root / conf["file"]).read_text())
    config["arch"] = str(arch.path_of(config, root))
    mix = generator.load_mix(w["traffic"])
    config["serving"].update(mix.get("serving", {}))   # sizes the mix needs
    return Cell(
        name=name, config=config, mix=mix,
        cell=json.loads((root / "bench" / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def smoke_cell(cell: Cell) -> Cell:
    """The cell at its CPU rehearsal size: the configuration's ``smoke``
    overrides (widths, serving sizes) and the cell's ``smoke`` rate."""
    c = {**cell.config, **cell.config["smoke"]["model"]}
    c["serving"] = {**cell.config["serving"], **cell.config["smoke"]["serving"]}
    return Cell(cell.name, c, cell.mix, {**cell.cell, **cell.cell["smoke"]},
                cell.end_to_end, cell.per_layer)


def trace_start(cell: Cell, seconds: float) -> float:
    """When a traced run's profiler starts: at the cell file's
    ``trace_at_share`` of the window, for traffic whose work can end before
    the window does; else ``TRACE_S`` before the window closes."""
    share = cell.cell.get("trace_at_share")
    if share is not None:
        return share * seconds
    return max(seconds - TRACE_S, seconds / 3)


def read_metric(name: str, run: "RunData"):
    """Load ``bench/metrics/<name>.py`` and return its reading (None where
    it finds nothing to read)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# ---------------------------------------------------------- the program
def train_ranker(mix: dict, smoke: bool):
    """The PARS ranker at BERT-base width, trained pairwise from the mix's
    fixed ranker seed (the same ranker for every traffic seed)."""
    from bench.traffic import generator
    from repro.configs.pars_bert_base import CONFIG, smoke_config
    from repro.core.predictor import TrainSettings, train_predictor
    from repro.core.predictor.tokenizer import HashTokenizer
    r = mix["ranker"]
    pcfg = smoke_config() if smoke else CONFIG
    steps = 4 if smoke else r["steps"]
    prompts, lengths = generator.training_set(
        generator.load_mix(r["mix"]), r["train_prompts"] // (16 if smoke else 1),
        r["seed"], pcfg.max_len - 1)
    return train_predictor(
        prompts, lengths,
        settings=TrainSettings(method="pairwise", epochs=1,
                               batch_size=r["batch_size"],
                               pairs_per_epoch=steps * r["batch_size"],
                               seed=r["seed"]),
        tokenizer=HashTokenizer(pcfg.vocab_size, pcfg.max_len), pcfg=pcfg)


class Compiles:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def compile_cache_dir() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else one fixed directory inside the checkout."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or str(ROOT / ".jax_cache")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------- probes
@dataclass
class Probe:
    """Host-side readings around the calls into each layer. Spans are
    ``TraceAnnotation``s, so a traced run sees them beside the device."""
    trace_dir: Optional[Path] = None
    trace_at: float = 0.0
    steps: int = 0
    occupancy: List[float] = field(default_factory=list)
    admitted: Dict[int, float] = field(default_factory=dict)
    delivered: Dict[int, float] = field(default_factory=dict)
    decode_s: List[float] = field(default_factory=list)
    prefill_s: float = 0.0
    prefill_tokens: int = 0
    scorer_s: float = 0.0
    scored: int = 0
    scorer_batch_max: int = 0
    profiling: bool = False     # the profiler records
    tracing: bool = False       # inside the traced window: work is counted
    traced: bool = False
    trace_flops: float = 0.0
    #: each traced decode call: (contexts, counts), ``counts`` the change
    #: of the architecture's ``COUNTERS`` across the call
    decode_calls: List[tuple] = field(default_factory=list)
    max_batch: int = 1

    def attach(self, engine, shapes, prompt_ids, counters) -> None:
        import jax
        core, be = engine.core, engine.backend
        self.max_batch = engine.scheduler.max_batch
        ann = jax.profiler.TraceAnnotation

        step, decode, prefill = core.step, be.decode, be.prefill
        schedule, add = core.scheduler.schedule, core.scheduler.add_requests

        def step_w(now):
            with ann("bench.step"):
                return step(now)

        def schedule_w(now):
            with ann("bench.schedule"):
                return schedule(now)

        def add_w(reqs):
            t = core.clock.now()
            for r in reqs:
                self.delivered.setdefault(r.req_id, t)
            return add(reqs)

        def decode_w(now):
            traced = self.tracing
            if traced:
                ctx = [be._pos[r.req_id] + 1 for r in be.slot_req
                       if r is not None and core.decode_ready(r)]
                before = [getattr(be, k) for k in counters]
            t0 = time.perf_counter()
            with ann("bench.decode"):
                out = decode(now)
            self.decode_s.append(time.perf_counter() - t0)
            if traced:
                counts = {k: getattr(be, k) - v
                          for k, v in zip(counters, before)}
                self.decode_calls.append((ctx, counts))
                self.trace_flops += shapes.decode(ctx, counts)["flops"]
            return out

        def prefill_w(chunks, now):
            self.prefill_tokens += sum(e - s for _, s, e in chunks)
            if self.tracing:
                for req, s, e in chunks:
                    n = len(prompt_ids(req))
                    self.trace_flops += shapes.prefill(
                        s, min(e, n), emits=e >= be.prefill_total(req))
            t0 = time.perf_counter()
            with ann("bench.prefill"):
                out = prefill(chunks, now)
            self.prefill_s += time.perf_counter() - t0
            return out

        core.step = step_w
        core.scheduler.schedule = schedule_w
        core.scheduler.add_requests = add_w
        be.decode = decode_w
        be.prefill = prefill_w

    def scorer(self, score):
        """Times the ranker's calls as the policy makes them."""
        import jax

        def run(prompts):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.scorer"):
                out = np.asarray(score(prompts))
            self.scorer_s += time.perf_counter() - t0
            self.scored += len(prompts)
            self.scorer_batch_max = max(self.scorer_batch_max, len(prompts))
            return out
        return run

    def reset(self) -> None:
        """Forget what the warm-up recorded."""
        self.steps, self.prefill_s, self.prefill_tokens = 0, 0.0, 0
        self.scorer_s, self.scored, self.scorer_batch_max = 0.0, 0, 0
        for f in (self.occupancy, self.decode_s):
            f.clear()
        for d in (self.admitted, self.delivered):
            d.clear()

    def on_step(self, core, now: float) -> None:
        import jax
        self.steps += 1
        running = core.scheduler.running
        self.occupancy.append(len(running) / self.max_batch)
        for r in running:
            if r.req_id not in self.admitted and r.start_time is not None:
                self.admitted[r.req_id] = r.start_time
        if self.trace_dir is None:
            return
        if not self.traced and not self.tracing and now >= self.trace_at:
            jax.profiler.start_trace(str(self.trace_dir))
            self.profiling = self.tracing = True
        elif self.tracing and now >= self.trace_at + TRACE_S:
            self.tracing, self.traced = False, True

    def stop(self) -> None:
        """Stop the profiler once the loop has ended."""
        import jax
        if self.profiling:
            jax.profiler.stop_trace()
            self.profiling = False
        if self.tracing:
            self.tracing, self.traced = False, True


# ------------------------------------------------------------ warm-up
#: Request ids of the warm-up bursts start here, clear of any window's.
WARM_IDS = 1 << 40


def warm_bursts(sizes, chunk: int, max_batch: int,
                cache_len: int) -> List[list]:
    """Bursts of (prompt tokens, output tokens) that, served one after the
    other, reach every dispatch shape a window of this traffic can reach.
    ``sizes`` are the traffic's prompt lengths as the engine pads them.

    * For each size up to ``chunk``: bursts of 1 to ``chunk // size``
      requests due together, so one step prefills each group size;
    * each size above ``chunk`` once: prefilled a chunk at a time;
    * a staircase of ``max_batch`` requests of the smallest size whose
      outputs end one token apart, once every prompt is in: decode then runs
      every batch from ``max_batch`` down to 1. An output is held to what
      the cache has room for after the padded prompt; where that cuts it,
      the prompts, done a chunk budget apart, still reach every batch on
      the way in and end apart on the way out.
    """
    small = sorted(s for s in sizes if s <= chunk)
    out = [[(s, 2)] * n for s in small
           for n in range(1, min(chunk // s, max_batch) + 1)]
    out += [[(s, 2)] for s in sorted(sizes) if s > chunk]
    s0 = small[0] if small else min(sizes)
    base = -(-max_batch * s0 // chunk) + 4
    room = cache_len - s0 + 1
    out.append([(s0, min(base + i, room)) for i in range(max_batch)])
    return out


def hit_bursts(sizes, chunk: int, block: int, max_batch: int) -> List[list]:
    """Bursts of (words of a shared document, words of its own, output
    tokens) that reach every continuation chunk a prefix-cache hit can make.
    ``sizes`` are the traffic's prompt lengths as the engine pads them.

    A hit leaves ``r`` positions of a padded prompt to compute: any multiple
    of ``block`` up to ``chunk`` (or up to the size, less a block). The core
    packs whole remainders into a step's ``chunk`` budget, so up to
    ``chunk // r`` of them share one dispatch, padded to a power of two.
    For each size the document is served first, its whole blocks cached;
    then, for each ``r`` and padded group, that many requests of the size
    whose first ``size - r`` tokens are the document's.
    """
    out = []
    for s in sorted(sizes):
        out.append([(s - 2, 0, 2)])                  # the document: s - 1
        for r in range(block, min(s - block, chunk) + 1, block):
            n_max = min(chunk // r, max_batch)
            groups = sorted({1 << (n - 1).bit_length()
                             for n in range(1, n_max + 1)})
            out += [[(s - r - 1, r, 2)] * min(g, n_max) for g in groups]
    return out


def warm(engine, bursts, scorer, scorer_batches: int, hits=()) -> None:
    """Serve ``bursts`` (of :func:`warm_bursts`), then ``hits`` (of
    :func:`hit_bursts`, over one document), through the engine's own serving
    loop, each to the end, then drop what they left in the prefix cache; and
    call the scorer once at every batch size up to ``scorer_batches``."""
    from bench.traffic.generator import filler, stream
    from repro.core.scheduler.request import Request
    from repro.serving.core import WallClock
    core = engine.core
    rng = stream("warm-up")
    doc = filler(rng, max(w for b in hits for w, _, _ in b)).split() \
        if hits else []
    rid = WARM_IDS
    prompts = [[(filler(rng, tokens - 1), out) for tokens, out in burst]
               for burst in bursts]
    prompts += [[(" ".join(doc[:shared] + filler(rng, own).split()), out)
                 for shared, own, out in burst] for burst in hits]
    for burst in prompts:
        reqs = []
        for prompt, out in burst:
            reqs.append(Request(req_id=rid, prompt=prompt, arrival_time=0.0,
                                prompt_len=1 + len(prompt.split()),
                                true_length=out))
            rid += 1
        core.clock = WallClock()
        core.submit(reqs)
        core.run()
        core.finished.clear()
    core.allocator.clear_cache()
    for n in range(1, scorer_batches + 1):
        scorer([filler(rng, 8)] * n)


# ------------------------------------------------------------- the run
@dataclass
class RunData:
    """What a metric reader reads."""
    cell: Cell
    seconds: float
    requests: list          # program Requests due in the window
    end: float              # clock time the loop stopped
    setup_s: float
    probe: Probe
    shapes: object          # the architecture module's work counts
    peak: dict
    trace: Optional[dict] = None
    #: every integer counter of the backend: its change over the window
    counters: Dict[str, int] = field(default_factory=dict)

    @property
    def limits(self) -> dict:
        return self.cell.cell["limits"]


class Session:
    """The program built for one cell and seed, warmed for ``specs`` (the
    traffic it will be offered), ready to serve windows."""

    def __init__(self, cell: Cell, seed: int, specs, *, smoke: bool,
                 trace_dir: Optional[Path] = None, trace_at: float = 0.0):
        t0 = time.perf_counter()
        import jax

        from bench import arch, roofline, weights
        from bench.reference import bucket, encode
        from repro.core.predictor.tokenizer import HashTokenizer
        from repro.core.scheduler.policies import make_policy
        from repro.core.scheduler.scheduler import Scheduler
        from repro.serving import Engine, ServingConfig
        from repro.serving.kv_cache import BlockAllocator

        self.cell, self.seed, self.smoke = cell, seed, smoke
        c, sv = cell.config, cell.config["serving"]
        self.device = jax.devices()[0]
        self.peak = roofline.PEAKS["TPU v5 lite"] if smoke else \
            roofline.peaks(self.device.device_kind)
        self.arch = arch.load(c)
        self.shapes = self.arch.shapes(c)
        self.cache_dir = "off (rehearsal)" if smoke else compile_cache_dir()
        self.compiles = Compiles()
        cfg = self.arch.program_config(c)
        # the ranker trains first: its training step needs more of the chip
        # than is left beside the model's weights, and frees it when done
        self.ranker = train_ranker(cell.mix, smoke)
        t_ranker = time.perf_counter() - t0
        self.params = weights.make(c, seed)
        jax.block_until_ready(self.params)
        t_weights = time.perf_counter() - t0 - t_ranker

        self.probe = Probe(trace_dir=trace_dir, trace_at=trace_at)
        self.policy = make_policy("pars", self.probe.scorer(self.ranker.score))
        self.prompt_len = pl = sv["prompt_len"]
        self.engine = Engine(
            cfg, self.params,
            Scheduler(policy=self.policy, max_batch=sv["max_batch"]),
            cache_len=sv["cache_len"], prompt_len=pl,
            tokenizer=HashTokenizer(cfg.vocab_size, pl),
            allocator=BlockAllocator(sv["kv_blocks"], sv["block_size"]),
            record_tokens=True, paged=True,
            config=ServingConfig(
                prefill_chunk_tokens=sv["prefill_chunk_tokens"],
                prefix_caching=sv["prefix_caching"],
                rerank_every_steps=sv["rerank_every_steps"],
                record_token_times=True))
        self.ids: Dict[int, list] = {}
        self.probe.attach(self.engine, self.shapes,
                          lambda r: self.ids[r.req_id], self.arch.COUNTERS)
        self.counters: Dict[str, int] = {}

        ids = [encode(s.prompt, cfg.vocab_size, pl) for s in specs]
        sizes = {bucket(len(i), pl) for i in ids}
        self.bursts = warm_bursts(sizes, sv["prefill_chunk_tokens"],
                                  sv["max_batch"], sv["cache_len"])
        # prompts that share a first block can hit the prefix cache
        heads = {tuple(i[:sv["block_size"]]) for i in ids}
        self.hits = (hit_bursts(sizes, sv["prefill_chunk_tokens"],
                                sv["block_size"], sv["max_batch"])
                     if sv["prefix_caching"] and len(heads) < len(ids)
                     else [])
        n0 = self.compiles.n
        warm(self.engine, self.bursts, self.ranker.score,
             cell.cell["scorer_batches"], self.hits)
        self.probe.reset()
        self.setup_s = time.perf_counter() - t0
        log(f"[{cell.name} seed={seed}] set-up {self.setup_s:.2f} s (weights "
            f"{t_weights:.2f} s, ranker {t_ranker:.2f} s, warm-up "
            f"{self.compiles.n - n0} programs compiled or loaded, "
            f"{len(self.bursts)} bursts over prompt sizes {sorted(sizes)}, "
            f"{len(self.hits)} over prefix hits); "
            f"compile cache {self.cache_dir}")

    def serve(self, specs, seconds: float, *, id_base: int = 0):
        """Offer ``specs`` open-loop from now, then serve until each has
        finished or ``DRAIN_S`` past the window. Returns (requests, end)."""
        import jax

        from bench.reference import encode
        from repro.core.scheduler.request import Request
        from repro.serving.core import WallClock
        vocab = self.cell.config["vocab_size"]
        reqs = [Request(req_id=id_base + s.req_id, prompt=s.prompt,
                        arrival_time=s.due, prompt_len=s.prompt_tokens,
                        true_length=s.true_length) for s in specs]
        for r in reqs:
            self.ids[r.req_id] = encode(r.prompt, vocab, self.prompt_len)
        core, probe, be = self.engine.core, self.probe, self.engine.backend
        c0 = _int_counters(be)
        n0, d0 = self.compiles.n, (be.prefill_dispatches,
                                   be.extend_dispatches, be.prefix_installs,
                                   core.rerank_count)
        core.clock = WallClock()
        core.submit(reqs)
        core.run(max_time=seconds + DRAIN_S, on_step=probe.on_step)
        end = core.clock.now()
        probe.stop()
        self.counters = {k: v - c0[k] for k, v in _int_counters(be).items()}
        late = np.asarray([probe.delivered[r.req_id] - r.arrival_time
                           for r in reqs if r.req_id in probe.delivered])
        done = sum(r.finish_time is not None for r in reqs)
        d = [a - b for a, b in zip((be.prefill_dispatches,
                                    be.extend_dispatches, be.prefix_installs,
                                    core.rerank_count), d0)]
        log(f"[{self.cell.name} seed={self.seed}] window {seconds:g} s, served "
            f"to {end:.2f} s: {done}/{len(reqs)} finished, {probe.steps} "
            f"steps, {self.compiles.n - n0} programs compiled or loaded inside "
            f"the window; generator lateness p50 "
            f"{np.median(late) * 1e3:.3f} ms p95 "
            f"{np.percentile(late, 95) * 1e3:.3f} ms; prefill dispatches "
            f"{d[0]}, extend {d[1]}, prefix hits {d[2]}, re-ranks {d[3]}; "
            f"largest scorer batch {probe.scorer_batch_max}")
        return reqs, end

    def conservation(self, reqs) -> dict:
        """Every request attempted finished with its whole stream or is
        still in the system (then it counts as failed); a drained system
        holds no KV block."""
        core = self.engine.core
        in_system = {r.req_id for r in (*core.scheduler.waiting,
                                        *core.scheduler.running,
                                        *core._pending)}
        finished = [r for r in reqs if r.finish_time is not None]
        return {
            "lost_requests": len(reqs) - len(finished) - len(in_system),
            "short_streams": sum(1 for r in finished
                                 if len(r.generated_tokens) != r.true_length
                                 or r.tokens_done != r.true_length),
            "leaked_kv_blocks": (core.allocator.used_blocks
                                 if not in_system else 0)}

    def close(self) -> None:
        """Free the program's state on the device. The probe's wrappers
        hold the backend, so its arrays are dropped by hand."""
        if self.engine is not None:
            be = self.engine.backend
            be.params = be.k_pool = be.v_pool = be.cache = None
        self.engine = self.params = self.ranker = self.policy = None
        gc.collect()


def _int_counters(backend) -> Dict[str, int]:
    """The backend's public integer attributes: its counters (and sizes,
    which do not change)."""
    return {k: v for k, v in vars(backend).items()
            if type(v) is int and not k.startswith("_")}


def _sample(finished, seed: int) -> list:
    """The longest finished request, then others drawn from the seed, until
    the sample holds ``SAMPLE_TOKENS`` served tokens."""
    if not finished:
        return []
    from bench.traffic.generator import stream
    longest = max(finished, key=lambda r: (r.true_length, -r.req_id))
    rest = [r for r in finished if r is not longest]
    order = stream("sample", seed).permutation(len(rest))
    out, total = [longest], longest.true_length
    for i in order:
        if total >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        total += rest[i].true_length
    return out


def check_served(c: dict, seed: int, sample, prompt_len: int,
                 quant: bool = False) -> dict:
    """Widest gap by which a served token's logit lies below the reference's
    best, over the sample; with ``quant`` the control's reading instead: the
    gap of the token the float8 pass puts first, at the same positions."""
    from bench import arch
    from bench.reference import gaps, served_sequence
    reference = arch.load(c).Reference
    ref = reference(c, seed)
    ctl = reference(c, seed, quant=True) if quant else None
    worst, n = 0.0, 0
    for prompt, served in sample:
        seq, first = served_sequence(prompt, served, c["vocab_size"],
                                     prompt_len)
        rows = np.arange(first, first + len(served))
        lg = ref.logits(seq, rows)
        chosen = served if ctl is None else ctl.logits(seq, rows).argmax(1)
        worst = max(worst, float(gaps(lg, chosen).max()))
        n += len(served)
    return {"gap": worst, "tokens": n}


def run(name: str, seed: int, seconds: float, trace: bool, *,
        smoke: bool = False, work_dir: Optional[Path] = None,
        root: Path = ROOT) -> dict:
    """One run of cell ``name`` of ``root/BENCHMARK.json``; returns the
    result line's object. With ``smoke`` the cell runs at its CPU rehearsal
    size on whatever device JAX has (the caller decides whether that device
    may report)."""
    from bench.traffic import generator
    cell = load_cell(name, root)
    if smoke:
        cell = smoke_cell(cell)
    specs = generator.trace(cell.mix, cell.cell["rate_per_s"], seconds, seed)
    if smoke:
        for s in specs:
            s.true_length = min(s.true_length,
                                cell.config["serving"]["max_output_tokens"])
    trace_dir = (work_dir or ROOT / ".bench_work") / f"trace-{name}-{seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    sess = Session(cell, seed, specs, smoke=smoke,
                   trace_dir=trace_dir if trace else None,
                   trace_at=trace_start(cell, seconds))
    reqs, end = sess.serve(specs, seconds)
    stats = sess.device.memory_stats() or {}
    probe = sess.probe
    trace_red = None
    if trace and probe.traced:
        from bench import trace as tr
        trace_red = tr.reduce_dir(trace_dir, shapes=sess.shapes,
                                  peak=sess.peak,
                                  decode_calls=probe.decode_calls,
                                  flops=probe.trace_flops, clip_s=TRACE_S)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if trace_red:
        log(f"[{name} seed={seed}] traced {trace_red['window_s']:.3f} s: busy "
            f"{trace_red['busy_s']:.3f} s; decode set by "
            f"{trace_red.get('decode_bound', 'no decode program found')}")
    data = RunData(cell=cell, seconds=seconds, requests=reqs, end=end,
                   setup_s=sess.setup_s, probe=probe, shapes=sess.shapes,
                   peak=sess.peak, trace=trace_red, counters=sess.counters)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = read_metric(m["name"], data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    kept = sess.conservation(reqs)
    finished = [r for r in reqs if r.finish_time is not None]
    sample = [(r.prompt, list(r.generated_tokens))
              for r in _sample(finished, seed)]
    import jax
    dev = sess.device
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if trace_red:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
    sess.close()

    t_ref = time.perf_counter()
    served = check_served(cell.config, seed, sample, sess.prompt_len)
    log(f"[{name} seed={seed}] reference over {len(sample)} requests, "
        f"{served['tokens']} served tokens, in "
        f"{time.perf_counter() - t_ref:.2f} s")
    limits = cell.cell["checks"]
    checks = {k: {"value": v, "limit": 0} for k, v in kept.items()}
    checks["sample_shortfall_tokens"] = {
        "value": max(limits["sample_tokens_min"] - served["tokens"], 0),
        "limit": 0}
    checks["logit_gap"] = {"value": served["gap"],
                           "limit": limits["logit_gap"]}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    out = {"correct": correct, "attempted": len(reqs),
           "failed": len(reqs) - len(finished), "metrics": metrics,
           "device": device}
    if trace_red:
        out["breakdown"] = trace_red["breakdown"]
    out["checks"] = checks
    return out


def check_lines(result: dict) -> List[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in result["checks"].items()]
