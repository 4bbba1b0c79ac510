"""One run of one cell: set-up, warm-up, the measured window over the
engine's own batch loop, and what the run's result line is made of.

Everything particular to a cell is data, found by name:
``configs/<config>.json`` (the router deployment and its correctness
limits, naming the reference module beside it), ``traffic/<mix>.json``
(the generator's parameters and the engine knobs the mix sets) and
``metrics/<metric>.py`` (one reader per per-layer metric). The window
drives ``AdaParseEngine._overlapped_batches`` — the loop
``AdaParseEngine.run`` uses — over an endless stream of fresh batch
keys; the stages are timed by wrapping the methods of this run's own
engine instance, never by editing the program.

A configuration may bring its own parser model: ``"parser_model":
{"reference": "<module>", ...widths...}`` names ``configs/<module>.py``
and gives it the other keys as ``widths``. That module builds, runs and
checks the model; the harness calls it and nothing else knows of it:

- ``init(widths, seed)``: the seeded weights, made on the device
  (required);
- ``backend(widths, weights, name)``: the program's parser backend,
  registered under the configuration's ``expensive`` parser for the
  run, before the engine is built, so that the engine's own
  ``complete_batch`` runs it (required);
- ``warm(backend)``: compile what the window's batches run, in set-up;
- ``keep(backend, row)``: after each ``complete_batch``, what to keep
  of that batch for the check; stored as ``row["parser"]`` and dropped
  with the batch's other heavy outputs when it is not sampled;
- ``readings(sample, weights, widths) -> {name: number}``: for every
  sampled batch, the module's numbers against its own reference
  (``sample["parser"]`` is what ``keep`` returned); names in the
  module's ``EXACT`` are summed over the batches, the others folded by
  their largest, and each is judged by its limit in the configuration's
  ``limits``. The control's samples (``calibrate.py``) carry no
  ``parser``: there the module puts its reference one precision lower
  in the program's place.

A traced run (``trace``) turns the program's observability plane on for
the window alone, reads its spans from the profiler's trace
(``spantrace``) into ``Run.spans`` and ``breakdown["idle_by_span"]``,
and gives ``Run.counters``: the program's counters' change over the
window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

import flops
import reference as R
import traffic as T

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
#: batches run through the whole path before the window opens
WARM_BATCHES = 2
#: the first batch key of the measured window (warm-up keys count from 0)
WINDOW_KEY = 1 << 20
#: window batches kept for the reference, per stratum (probed or not)
CHECK_BATCHES = 2
#: the parser model's weights' stream (streams 6 and 7 seed the two
#: strata's samples)
PARSER_STREAM = 8


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise LookupError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                          f"named {name!r} (looked for {path})")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    if not path.is_file():
        raise LookupError(f"no module at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return load_module(BENCH / "metrics" / f"{name}.py").read


def resolve_cell(bench: dict, workload: str | None, cell: str | None):
    """(cell name, config, traffic, metric specs for the cell) from a
    ``BENCHMARK.json`` workload, or from ``CONFIG/TRAFFIC`` for a cell
    that has no entry yet (it gets every metric that applies)."""
    if cell:
        cname, _, tname = cell.partition("/")
        name = f"{cname}/{tname}"
    else:
        match = [w for w in bench["workloads"] if w["name"] == workload]
        if not match:
            raise LookupError(f"no workload named {workload!r} in "
                              f"BENCHMARK.json")
        cname, tname, name = (match[0]["config"], match[0]["traffic"],
                              workload)
    config, traffic = load_json("configs", cname), load_json("traffic",
                                                             tname)

    def applies(m):
        return cell is not None or name in m.get("workloads", [name])

    return (name, config, traffic,
            [m for m in bench["end_to_end"] if applies(m)],
            [m for m in bench["per_layer"] if applies(m)])


def corpus_config(traffic: dict, seed: int):
    from repro.data.synthetic import CorpusConfig

    return CorpusConfig(n_docs=traffic["pool_docs"],
                        min_pages=traffic["min_pages"],
                        max_pages=traffic["max_pages"],
                        page_tokens=traffic["page_tokens"],
                        seed=seed % 2 ** 32, **traffic["corpus"])


def fit_stages(config: dict, traffic: dict, seed: int):
    """The CLS-I (and, for the ft router, CLS-II) logistic stages, fit on
    ``config["fit_docs"]`` seeded documents against the reference
    channel's BLEU: CLS-I learns whether the cheap extraction is valid
    (BLEU > 0.15), CLS-II whether the expensive parser beats it by more
    than 0.02. For the llm router the fit documents' first-page tokens
    and masks are kept too (``first_pages``), to centre the head on."""
    from repro.data.synthetic import Document

    corpus = traffic["corpus"]
    docs = T.make_pool(traffic, corpus, T.stream_seed(seed, 2), Document,
                       n_docs=config["fit_docs"])
    rng = np.random.RandomState(T.stream_seed(seed, 3))
    cheap = R.channel(docs, config["cheap"], corpus, rng)
    n = config["fit_bleu_tokens"]
    b_cheap = np.array([R.bleu(d.full_text(), _joined(p), n)
                        for d, p in zip(docs, cheap)])
    feats = np.stack([R.fast_features(p, corpus) for p in cheap])
    stages = {"cls1": R.fit_logistic(feats, b_cheap > 0.15)}
    if config["variant"] == "llm":
        stages["first_pages"] = tuple(map(np.stack, zip(*(
            R.first_page(p, config["encoder"]["max_len"]) for p in cheap))))
    if config["variant"] == "ft":
        exp = R.channel(docs, config["expensive"], corpus, rng)
        b_exp = np.array([R.bleu(d.full_text(), _joined(p), n)
                          for d, p in zip(docs, exp)])
        meta = np.stack([R.metadata(d) for d in docs])
        stages["cls2"] = R.fit_logistic(meta, b_exp > b_cheap + 0.02)
    return stages


def seeded_weights(config: dict, encoder, seed: int, stages: dict):
    """The encoder's weights from the seed, made on the device, with the
    head centred on the fit documents' first pages so that
    ``config["positive_share"]`` of them show a positive improvement."""
    import jax

    weights = jax.block_until_ready(encoder.init(config["encoder"], seed))
    return encoder.center_head(weights, config["encoder"],
                               *stages["first_pages"], config["cheap_index"],
                               config["expensive_index"],
                               config["positive_share"])


def _joined(pages):
    return (np.concatenate(pages) if sum(map(len, pages))
            else np.zeros(0, np.int32))


@dataclasses.dataclass
class ParserModel:
    """The parser model a configuration names: its module, the widths
    it is built at, and its seeded weights."""

    module: object
    widths: dict
    weights: object

    def hook(self, name: str):
        """The module's optional hook ``name``, or None."""
        return getattr(self.module, name, None)


def parser_model(config: dict, seed: int) -> ParserModel | None:
    """The configuration's ``parser_model``, built from the seed, or None
    for a configuration without one."""
    import jax

    spec = config.get("parser_model")
    if spec is None:
        return None
    module = load_module(BENCH / "configs" / f"{spec['reference']}.py")
    widths = {k: v for k, v in spec.items() if k != "reference"}
    weights = jax.block_until_ready(
        module.init(widths, T.stream_seed(seed, PARSER_STREAM)))
    return ParserModel(module, widths, weights)


def build_router(config: dict, stages: dict, weights):
    from repro.configs.base import EncoderConfig
    from repro.core.router import AdaParseRouter, LinearStage

    cls1 = LinearStage(*stages["cls1"])
    if config["variant"] == "llm":
        enc = EncoderConfig(name=config["name"], **config["encoder"])
        return AdaParseRouter("llm", cls1, None, enc_cfg=enc,
                              enc_params=weights)
    return AdaParseRouter("ft", cls1, LinearStage(*stages["cls2"]))


class Instrument:
    """Host clock (and, when tracing, profiler annotations) around each
    stage call of one engine instance, per batch key."""

    def __init__(self, engine, tracing: bool, probe_len: int, cls1,
                 valid_threshold: float, keep=None):
        import jax

        self.probe_len = probe_len
        #: the parser model's ``keep`` hook, bound to its backend
        self.keep = keep
        self.cls1, self.valid_threshold = cls1, valid_threshold
        self.rows: dict[int, dict] = {}
        self.current: int | None = None
        self.completed: int | None = None
        self._annotate = jax.profiler.TraceAnnotation if tracing else None
        for name in ("prepare_batch", "route_batch", "complete_batch"):
            setattr(engine, name, getattr(self, "_" + name)(
                getattr(engine, name)))
        if engine.probe is not None:
            engine.probe.score_records = self._score_records(
                engine.probe.score_records)

    def span(self, name: str):
        return (self._annotate(name) if self._annotate
                else contextlib.nullcontext())

    def row(self, key) -> dict:
        return self.rows.setdefault(key, {})

    def _prepare_batch(self, orig):
        def prepare_batch(docs, batch_key=None):
            t0 = time.perf_counter()
            with self.span("bench.prepare"):
                prep = orig(docs, batch_key=batch_key)
            t1 = time.perf_counter()
            self.row(batch_key).update(
                t_prepare=t0, prepare_s=t1 - t0, n_docs=len(docs),
                stream_tokens=[sum(map(len, p)) for p in prep.extracted],
                first_len=[len(p[0]) if p else 0 for p in prep.extracted])
            return prep
        return prepare_batch

    def _route_batch(self, orig):
        def route_batch(prep):
            self.current = prep.batch_key
            t0 = time.perf_counter()
            with self.span("bench.route"):
                plan = orig(prep)
            dt = time.perf_counter() - t0
            # which routed documents CLS-I forced (its logistic stage on
            # the program's own features) and which the ranking chose
            invalid = R.logistic(prep.fast, *self.cls1) < self.valid_threshold
            sel = np.asarray(plan.expensive_idx, np.int64)
            self.row(prep.batch_key).update(
                route_s=dt, n_invalid=int(invalid.sum()),
                n_routed=len(sel), n_forced=int(invalid[sel].sum()))
            return plan
        return route_batch

    def wrap_route_step(self, engine) -> None:
        """Keep the route step's own outputs (scores, improvement) for
        the batch being routed; the step is built lazily by the first
        routed batch, so this runs after warm-up."""
        orig = engine._route_step

        def route_step(*args):
            out = orig(*args)
            self.row(self.current).update(route_out=out,
                                          improvement=out["improvement"])
            return out
        engine._route_step = route_step

    def _complete_batch(self, orig):
        def complete_batch(prep, plan, node_id=0, ingest_engine=None):
            key = prep.batch_key
            row = self.row(key)
            row["probe_s"] = 0.0
            self.current = key
            t0 = time.perf_counter()
            with self.span("bench.complete"):
                records = orig(prep, plan, node_id=node_id,
                               ingest_engine=ingest_engine)
            dt = time.perf_counter() - t0
            row.update(t_complete=t0, complete_s=dt - row["probe_s"],
                       prep=prep, plan=plan,
                       records=records,
                       complete_ok=[r.doc_id for r in records]
                       == [d.doc_id for d in prep.docs])
            if self.keep is not None:
                row["parser"] = self.keep(row)
            self.completed = key
            return records
        return complete_batch

    def _score_records(self, orig):
        def score_records(docs, records):
            t0 = time.perf_counter()
            with self.span("bench.probe"):
                quality = orig(docs, records)
            row = self.row(self.current)
            row.update(probe_s=time.perf_counter() - t0, quality=quality,
                       probe_tokens=(
                           [min(sum(map(len, d.pages)), self.probe_len)
                            for d in docs],
                           [min(sum(map(len, r.pages)), self.probe_len)
                            for r in records]))
            return quality
        return score_records


class Reservoir:
    """A seeded uniform sample of ``size`` items from a stream."""

    def __init__(self, size: int, seed: int):
        self.size, self.seen, self.items = size, 0, []
        self.rng = np.random.RandomState(seed)

    def offer(self, item) -> tuple[bool, list]:
        """(whether ``item`` was kept, the items it displaced)."""
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return True, []
        j = self.rng.randint(self.seen)
        if j < self.size:
            out, self.items[j] = self.items[j], item
            return True, [out]
        return False, []


def warm_shapes(engine, config: dict, traffic: dict, pool: list,
                warm_parser=None) -> None:
    """Compile (or load from the cache) every program the window's
    batches use, beyond the route step that the first batches compile:
    the prepare stage at each packed width the mix produces, the parser
    model's programs (its ``warm`` hook, bound to its backend, as
    ``warm_parser``), and the probe's scorer at every padded group size
    a batch of k with at most floor(alpha*k) expensive records can
    give."""
    import jax

    from repro.core import features as F
    from repro.core.engine import ParseRecord

    k = config["batch_size"]
    max_len = (config["encoder"]["max_len"] if config["variant"] == "llm"
               else None)
    for width in traffic["packed_widths"]:
        pages = [[np.full(width // 2 + 1 if i == 0 else 8, R.WORD_LO,
                          np.int32)] for i in range(k)]
        out = F.prepare_routing_inputs(pages, engine.ccfg, max_len=max_len,
                                       mode=engine.cfg.feature_kernel)
        jax.block_until_ready([o for o in out if o is not None])
    if warm_parser is not None:
        warm_parser()
    if engine.probe is None:
        return
    pads, docs = set(), pool[:k]
    for n_exp in range(R.capacity(config["alpha"], k) + 1):
        groups = {_pow2(n) for n in (n_exp, k - n_exp) if n}
        if groups <= pads:
            continue
        pads |= groups
        recs = [ParseRecord(d.doc_id, config["expensive"] if i < n_exp
                            else config["cheap"], d.pages, 0.0)
                for i, d in enumerate(docs)]
        engine.probe.score_records(docs, recs)


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def log(msg: str) -> None:
    print(f"[cell] {msg}", file=sys.stderr, flush=True)


class Run:
    """What a per-layer metric reader sees of one finished run.

    ``batches`` are the rows of the batches emitted inside the window;
    ``calls`` are the rows of every batch the run instrumented, with the
    host clock's start of each stage call (``t_prepare``,
    ``t_complete``) and the sizes it saw; ``trace`` is the reduced
    profiler trace (``devtrace.Reduced``) or None; ``spans`` the
    program's own spans in it (``spantrace.Spans``) or None; and
    ``counters`` the change of the program's counters
    (``repro.core.obs``) over a traced window, by name."""

    def __init__(self, config, traffic, batches, calls, window, compiles,
                 peak, trace, spans=None, counters=None):
        self.config, self.traffic = config, traffic
        self.batches, self.calls = batches, calls
        self.t0, self.t_end = window
        self.window_s = self.t_end - self.t0
        self.compiles, self.peak, self.trace = compiles, peak, trace
        self.spans, self.counters = spans, counters or {}

    def started(self, stage: str) -> list[dict]:
        """Rows whose ``stage`` call started inside the window."""
        key = f"t_{stage}"
        return [r for r in self.calls
                if key in r and self.t0 <= r[key] <= self.t_end]

    def roofline(self, op: str, work: list) -> float | None:
        """Percent of the roofline of ``work`` ((operations, bytes) per
        call) over the device seconds of the operation ``op`` (its name
        in the trace, without the ``.N`` suffix); None when the trace
        holds no such operation or there is no work."""
        if self.trace is None or self.peak is None or not work:
            return None
        seconds, n = self.trace.ops.get(op, (0.0, 0))
        if not n:
            return None
        ops = sum(o for o, _ in work)
        moved = sum(b for _, b in work)
        return flops.roofline_share(ops, moved, seconds, self.peak)[0]


def device_peak(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise LookupError(f"no peaks for device kind {kind!r} in "
                          f"peaks.json")
    return peaks[kind]


def run_cell(name, config, traffic, e2e, per_layer, *, seed: int,
             seconds: float, trace: bool, t_start: float,
             keep_trace: pathlib.Path | None = None) -> dict:
    """Set up, warm up, measure for ``seconds``, check, and return the
    result line's object. A parser model's backend stands under the
    configuration's ``expensive`` name from before the engine is built
    until the window has closed."""
    import jax

    from repro.device import ensure_compile_cache

    # every program, however quick to compile, goes to the cache, so a
    # second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"{name} seed {seed}: compile cache {ensure_compile_cache()}")
    parser = parser_model(config, seed)
    with contextlib.ExitStack() as program:
        backend = None
        if parser is not None:
            backend = parser.module.backend(parser.widths, parser.weights,
                                            name=config["expensive"])
            program.enter_context(standing_in(backend, config["expensive"]))
        return _run_cell(name, config, traffic, e2e, per_layer, parser,
                         backend, program.close, seed=seed, seconds=seconds,
                         trace=trace, t_start=t_start, keep_trace=keep_trace)


@contextlib.contextmanager
def standing_in(backend, name: str):
    """``backend`` registered under ``name`` in the program's registry of
    parser backends, the one it replaced restored on exit."""
    from repro.core import backends

    if backend.info.name != name:
        raise ValueError(f"the parser model's backend is named "
                         f"{backend.info.name!r}, not {name!r}")
    before = backends.get_backend(name)
    backends.register_backend(backend, overwrite=True)
    try:
        yield backend
    finally:
        backends.register_backend(before, overwrite=True)


def _run_cell(name, config, traffic, e2e, per_layer, parser, backend,
              release, *, seed, seconds, trace, t_start, keep_trace) -> dict:
    """``run_cell`` past the compile cache and the parser model: the
    engine, warm-up, window and check; ``release()`` gives the parser
    backend's place back once the window has closed."""
    import jax

    from repro.core import obs
    from repro.core.engine import AdaParseEngine, EngineConfig
    from repro.core.quality import QualityProbe, QualityProbeConfig
    from repro.data.synthetic import Document

    import check
    import devtrace
    import spantrace

    compiled: list[tuple[float, str]] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiled.append(
            (time.perf_counter(), kw.get("fun_name", "?")))
        if ev == COMPILE_EVENT else None)
    cache = {CACHE_HIT: 0, CACHE_MISS: 0}
    jax.monitoring.register_event_listener(
        lambda ev, **kw: cache.__setitem__(ev, cache[ev] + 1)
        if ev in cache else None)
    dev = jax.devices()[0]
    peak = device_peak(dev.device_kind) if dev.platform == "tpu" else None
    llm = config["variant"] == "llm"
    encoder = (load_module(BENCH / "configs" / f"{config['reference']}.py")
               if llm else None)

    t = time.perf_counter()
    pool = T.make_pool(traffic, traffic["corpus"], seed, Document)
    stages = fit_stages(config, traffic, seed)
    weights = seeded_weights(config, encoder, seed, stages) if llm else None
    router = build_router(config, stages, weights)
    engine_seed, probe_seed = T.stream_seed(seed, 4), T.stream_seed(seed, 5)
    engine = AdaParseEngine(
        EngineConfig(alpha=config["alpha"], batch_size=config["batch_size"],
                     cheap=config["cheap"], expensive=config["expensive"],
                     seed=engine_seed,
                     prefetch_depth=traffic["prefetch_depth"]),
        router, corpus_config(traffic, seed),
        probe=QualityProbe(QualityProbeConfig(
            probe_rate=traffic["probe_rate"], seed=probe_seed,
            max_len=traffic["probe_max_len"], metric="bleu")))
    log(f"inputs, routing stages and weights {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    hooks = {h: functools.partial(parser.hook(h), backend)
             for h in ("warm", "keep")
             if parser is not None and parser.hook(h) is not None}
    warm_shapes(engine, config, traffic, pool, hooks.get("warm"))
    inst = Instrument(engine, trace, traffic["probe_max_len"],
                      stages["cls1"], config["valid_threshold"],
                      hooks.get("keep"))
    max_len = config["encoder"]["max_len"] if llm else 0
    warm = engine._overlapped_batches(
        T.batches(pool, config["batch_size"], seed), 0)
    for _ in range(WARM_BATCHES):
        next(warm)
    warm.close()
    if llm:
        inst.wrap_route_step(engine)
    # The window's loop starts empty, with batch keys of its own. The
    # loop keeps whatever lead its prefetcher has (a warm-up that
    # compiled lets it run a batch further ahead, and every batch then
    # waits one device cycle longer), so the lead is set by the start,
    # not by what the warm-up happened to compile.
    gen = engine._overlapped_batches(
        T.batches(pool, config["batch_size"], seed, first_key=WINDOW_KEY), 0)
    log(f"warm-up {time.perf_counter() - t:.2f} s, {len(compiled)} programs "
        f"compiled or loaded; compile cache hits {cache[CACHE_HIT]}, "
        f"misses {cache[CACHE_MISS]}")

    if trace:
        # a directory of the run's own: runs in other processes trace
        # beside it
        (BENCH / "out").mkdir(parents=True, exist_ok=True)
        trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="trace-",
                                                  dir=BENCH / "out"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    samples = {s: Reservoir(CHECK_BATCHES, T.stream_seed(seed, 6 + s))
               for s in (0, 1)}
    counted: list[int] = []
    counters: dict = {}
    if trace:
        # the program's spans and counters, for the traced window alone
        obs.configure(True)
        base = obs.metrics().snapshot()
    with inst.span("bench.window_open"):
        t0 = time.perf_counter()
    n_compiled = len(compiled)
    # the window closes with the first batch emitted ``seconds`` after it
    # opened, and counts it: every batch counted is whole, and the rate
    # is not rounded to whole batches per ``seconds``
    try:
        while True:
            next(gen)
            t_end = time.perf_counter()
            key = inst.completed
            row = inst.rows[key]
            row["t_emit"] = t_end
            counted.append(key)
            kept, dropped = samples["quality" in row].offer(key)
            for k in ([key] if not kept else []) + dropped:
                _forget(inst.rows[k])
            if t_end - t0 >= seconds:
                break
    finally:
        gen.close()
        if trace:
            jax.profiler.stop_trace()
            counters = obs.diff(obs.metrics().snapshot(), base)["counters"]
            obs.configure(False)
    window_s = t_end - t0
    window_compiles = [f for c, f in compiled[n_compiled:] if c <= t_end]
    stats = dev.memory_stats() or {}
    rows = [inst.rows[k] for k in counted]
    docs = sum(r["n_docs"] for r in rows)
    failed = sum(r["n_docs"] for r in rows if not r["complete_ok"])
    latency = [r["t_emit"] - r["t_prepare"] for r in rows]
    emits = [t0] + [r["t_emit"] for r in rows]
    for r in rows:
        r["real_tokens"] = ([1 + min(n, max_len - 1) for n in r["first_len"]]
                            if llm else [])
    widths = sorted({max(128, _pow2(max(max(r["stream_tokens"]), max_len)))
                     for r in rows})
    log(f"window {window_s:.3f} s: {len(rows)} batches, {docs} documents, "
        f"longest wait for a batch {max(np.diff(emits)):.3f} s, "
        f"packed widths {widths}, programs compiled or loaded "
        f"{window_compiles}, device memory {stats}")
    log(routing_summary(rows))
    if set(widths) - set(traffic["packed_widths"]):
        log(f"packed widths {widths} exceed the warmed "
            f"{traffic['packed_widths']}")

    t = time.perf_counter()
    ref = check.Reference(config, traffic, engine_seed, probe_seed, stages,
                          weights, encoder, parser)
    sampled = [k for s in samples.values() for k in s.items]
    progs = [check.program_sample(inst.rows[k], config["variant"])
             for k in sampled]
    del engine, router, gen, backend, hooks
    inst.keep = None
    release()
    for r in inst.rows.values():
        _forget(r)
    probe_diff = sum(("quality" in r) != ref.probed(k)
                     for k, r in zip(counted, rows))
    numbers = check.fold([check.compare(p, ref) for p in progs], probe_diff,
                         ref.exact)
    correct, checks = check.judge(numbers, config["limits"])
    correct = correct and failed == 0 and docs > 0
    log(f"reference over {len(progs)} batches {time.perf_counter() - t:.2f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    result = {"correct": bool(correct), "attempted": docs, "failed": failed}
    if not trace:
        values = {"setup_s": t0 - t_start, "docs_per_s": docs / window_s,
                  "batch_p90_ms": 1000.0 * percentile(latency, 90)}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in e2e if m["name"] in values}
        result["device"] = device
    else:
        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        if keep_trace is not None and files:
            keep_trace.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], keep_trace / files[-1].name)
        events = devtrace.load(files[-1]) if files else []
        reduced = devtrace.reduce(events, window_s) if files else None
        spans = (spantrace.reduce(events + spantrace.load(files[-1]),
                                  window_s) if files else None)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(config, traffic, rows, list(inst.rows.values()),
                  (t0, t_end), len(window_compiles), peak, reduced, spans,
                  counters)
        result["metrics"] = {}
        for m in per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        if reduced is not None and reduced.busy_s:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            result["device"] = device
            result["breakdown"] = reduced.breakdown
            if spans is not None:
                result["breakdown"]["idle_by_span"] = devtrace._top(
                    spans.idle_by_span)
        else:
            result["device"] = device
    result["checks"] = checks
    return result


def routing_summary(rows: list[dict]) -> str:
    """Per window batch: documents CLS-I calls invalid, documents routed,
    of them forced by CLS-I and chosen by the ranking of improvements,
    and (llm router) the valid documents with a positive improvement."""
    def stat(vals):
        return (f"{np.mean(vals):.2f} ({min(vals)}..{max(vals)})"
                if vals else "-")

    forced = [r["n_forced"] for r in rows]
    ranked = [r["n_routed"] - r["n_forced"] for r in rows]
    positive = []
    for r in rows:
        if "improvement" in r:
            imp = np.asarray(r.pop("improvement"), np.float64)
            positive.append(int(np.sum((imp > R.POSITIVE_TAU)
                                       & (imp < R.CLS1_OVERRIDE))))
    return (f"routing per batch over {len(rows)} window batches: CLS-I "
            f"invalid {stat([r['n_invalid'] for r in rows])}, routed "
            f"{stat([r['n_routed'] for r in rows])}, forced by CLS-I "
            f"{stat(forced)}, chosen by the ranking {stat(ranked)}, valid "
            f"with a positive improvement {stat(positive)}")


HEAVY = ("prep", "plan", "records", "route_out", "parser")


def _forget(row: dict) -> None:
    for k in HEAVY:
        row.pop(k, None)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))
