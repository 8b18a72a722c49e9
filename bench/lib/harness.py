"""One run of one cell: set-up, the measured window, the trace, the check.

The system under test is driven only through its public serving path:
``IndexWriter`` -> ``SegmentedAnnIndex`` -> ``AnnService`` with
``search_batch`` (closed loop) or ``search_async`` (open loop).  From the
program the benchmark reads only its counters (``AnnService`` request and
launch counts, ``ExecutableCache.compiles``) and its device trace.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import queue
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench.lib import check, corpus as corpus_mod, loadgen, registry, tracing
from bench.lib.compiles import CompileCounter

# A request still unanswered this long after the window closed never came.
ANSWER_GRACE_S = 60.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def hbm(phase: str) -> None:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    if not any(stats):
        log(f"hbm {phase}: not reported by this backend")
        return
    top = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    log(f"hbm {phase}: bytes_in_use {top.get('bytes_in_use')} "
        f"peak_bytes_in_use {top.get('peak_bytes_in_use')} of {top.get('bytes_limit')}")


def memory_peak() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Window:
    """What the measured window did, request by request."""

    picks: np.ndarray           # pool index of each request's query
    due: np.ndarray             # seconds from the window start
    sent: np.ndarray
    done: np.ndarray            # nan where no answer came
    shed: np.ndarray            # bool: refused at admission (queue.Full)
    errors: np.ndarray          # bool: the call raised
    ids: List[Optional[np.ndarray]]
    scores: List[Optional[np.ndarray]]
    seconds: float              # first send to last answer, or the window if longer


@contextlib.contextmanager
def writer_ops(svc, plan: loadgen.Plan, start: float, span):
    """Carry out the plan's writer operations at their due times on a thread
    of their own, so they never hold up the queries; an operation that
    raises fails the run once the window is over."""
    if not plan.ops:
        yield
        return
    stop, raised = threading.Event(), []

    def loop() -> None:
        for due, op in plan.ops:
            if stop.wait(max(0.0, start + due - time.perf_counter())):
                return
            try:
                with span("bench.write"):
                    op(svc)
            except Exception as e:
                raised.append(e)

    t = threading.Thread(target=loop, name="bench-writer", daemon=True)
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join()
    if raised:
        raise RuntimeError(f"{len(raised)} writer operations raised") from raised[0]


def run_closed(svc, pool_q: np.ndarray, plan: loadgen.Plan, seconds: float, span) -> Window:
    """One client sends ``plan.batch`` queries through ``search_batch`` and
    sends the next batch when the last returned.  Every query of a batch is
    a request timed by its batch."""
    picks, due, done, ids_l, sc_l = [], [], [], [], []
    start = time.perf_counter()
    end = start + seconds
    with writer_ops(svc, plan, start, span):
        while True:
            t0 = time.perf_counter()
            if t0 >= end:
                break
            idx = plan.picks(plan.batch)
            with span("bench.request"):
                s, ids = svc.search_batch(pool_q[idx])
            t1 = time.perf_counter()
            picks.append(idx)
            due.append(np.full(len(idx), t0 - start))
            done.append(np.full(len(idx), t1 - start))
            ids_l.extend(ids)
            sc_l.extend(s)
    picks_a = np.concatenate(picks) if picks else np.zeros(0, int)
    due_a = np.concatenate(due) if due else np.zeros(0)
    done_a = np.concatenate(done) if done else np.zeros(0)
    n = len(picks_a)
    return Window(
        picks=picks_a, due=due_a, sent=due_a.copy(), done=done_a,
        shed=np.zeros(n, bool), errors=np.zeros(n, bool), ids=ids_l, scores=sc_l,
        seconds=max(float(done_a.max()) if n else seconds, seconds),
    )


def run_open(svc, pool_q: np.ndarray, plan: loadgen.Plan, seconds: float, span) -> Window:
    """Send single queries through ``search_async`` when due, whatever the
    service is doing; each request is timed from when it was due."""
    due = plan.due
    n = len(due)
    picks = plan.picks(n)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    shed = np.zeros(n, bool)
    errors = np.zeros(n, bool)
    futs: List[Any] = [None] * n

    def on_done(i: int) -> Callable[[Any], None]:
        def cb(_f) -> None:
            done[i] = time.perf_counter() - start
        return cb

    start = time.perf_counter()
    with writer_ops(svc, plan, start, span):
        for i in range(n):
            wait = start + due[i] - time.perf_counter()
            if wait > 0:
                with span("bench.idle"):
                    time.sleep(wait)
            sent[i] = time.perf_counter() - start
            try:
                fut = svc.search_async(pool_q[picks[i]])
            except queue.Full:
                shed[i] = True
                continue
            fut.add_done_callback(on_done(i))
            futs[i] = fut
        rest = start + seconds - time.perf_counter()
        if rest > 0:
            with span("bench.idle"):
                time.sleep(rest)
    ids_l: List[Optional[np.ndarray]] = [None] * n
    sc_l: List[Optional[np.ndarray]] = [None] * n
    deadline = start + seconds + ANSWER_GRACE_S
    for i, fut in enumerate(futs):
        if fut is None:
            continue
        try:
            s, ids = fut.result(timeout=max(0.0, deadline - time.perf_counter()))
        except TimeoutError:
            done[i] = np.nan
            continue
        except Exception as e:  # the service failed this request
            log(f"request {i} raised {type(e).__name__}: {e}")
            errors[i] = True
            done[i] = np.nan
            continue
        ids_l[i], sc_l[i] = ids[0], s[0]
    last = np.nanmax(done) if np.isfinite(done).any() else seconds
    return Window(
        picks=picks, due=due, sent=sent, done=done, shed=shed, errors=errors,
        ids=ids_l, scores=sc_l, seconds=max(float(last), seconds),
    )


def make_service(cfg: Dict[str, Any], corpus):
    """IndexWriter -> SegmentedAnnIndex -> AnnService, as the configuration
    states."""
    from repro.core import types
    from repro.core.segments import IndexWriter
    from repro.serve.ann_service import AnnService, AnnServiceConfig

    enc = cfg["encoding"]
    method = getattr(types, enc["class"])(**enc["args"])
    writer = IndexWriter(method, **cfg["writer"])
    # Rows arrive in chunks of the writer's buffer, so each flush builds
    # one segment of at most that many rows.
    step = cfg["writer"].get("max_buffered_docs") or len(corpus)
    for start in range(0, len(corpus), step):
        writer.add(corpus[start : start + step])
    return AnnService(writer=writer, service=AnnServiceConfig(**cfg["service"]))


def counters(svc, compile_counter: CompileCounter) -> Dict[str, int]:
    from repro.core import packed

    return {
        "queries": svc.queries_served, "batches": svc.batches,
        "async_launches": svc.async_launches, "rejected": svc.rejected,
        "exec_cache_compiles": packed.EXEC_CACHE.compiles,
        "backend_compiles": compile_counter.compiles,
    }


def plant_fault(svc, fault: str, n_docs: int) -> None:
    """Break the timed path under the harness (for the harness's own tests):
    ``alter`` changes one id of every answer where it is produced; ``half``
    leaves out the second half of every batch and answers it with the first
    half's rows."""
    inner = svc.search_batch

    def broken(queries, *a, **kw):
        s, ids = inner(queries, *a, **kw)
        s, ids = np.array(s), np.array(ids)
        if fault == "alter":
            ids[:, 0] = (ids[:, 0] + 1) % n_docs
        elif fault == "half":
            h = (len(ids) + 1) // 2
            if len(ids) > 1:
                ids[h:] = ids[: len(ids) - h]
                s[h:] = s[: len(ids) - h]
        return s, ids

    svc.search_batch = broken


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader may read."""

    cell: registry.Cell
    events: Optional[List[dict]]
    counters: Dict[str, int]      # deltas over the window
    peaks: Dict[str, Any]


def per_layer(cell: registry.Cell, ctx: MetricContext) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        value = registry.metric_reader(cell, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(cell: registry.Cell, win: Window, setup_s: float, recall: Optional[float]):
    ok = np.isfinite(win.done)
    lat_ms = (win.done[ok] - win.due[ok]) * 1e3
    values = {
        "qps": ok.sum() / win.seconds if win.seconds > 0 else None,
        "p50_ms": float(np.percentile(lat_ms, 50)) if lat_ms.size else None,
        "p95_ms": float(np.percentile(lat_ms, 95)) if lat_ms.size else None,
        "recall_at_10": recall,
        "setup_s": setup_s,
    }
    # A metric split by cells (``recall_at_10.lsh``) reads its base quantity.
    return {
        m["name"]: {"value": float(values[m["name"].split(".")[0]]), "unit": m["unit"]}
        for m in cell.end_to_end if values.get(m["name"].split(".")[0]) is not None
    }


def run(
    cell: registry.Cell, seed: int, seconds: float, trace: bool, t_start: float,
    control: bool = False, fault: Optional[str] = None,
) -> Dict[str, Any]:
    """One run; returns the result object (``check`` last)."""
    import jax

    cfg, mix = cell.config, cell.traffic
    counter = CompileCounter().install()
    devices = jax.local_devices()[: cell.chips]
    log(f"cell {cell.name} seed {seed} on {len(devices)} x {devices[0].device_kind}"
        f"{' (precision control: ' + json.dumps(cfg['control']) + ')' if control else ''}"
        f"{' (fault: ' + fault + ')' if fault else ''}")

    # The rows wait on the host while the program runs, so the device holds
    # only what the program keeps.
    corpus = np.asarray(corpus_mod.make_corpus(seed, cfg["corpus"]))
    n_docs = int(corpus.shape[0])
    pool_q = corpus[corpus_mod.pool_rows(seed, n_docs, int(mix["pool"]))]
    hbm("corpus")
    if control:
        return run_control(cell, seed, corpus, pool_q, devices)
    svc = make_service(cfg, corpus)
    if fault:
        plant_fault(svc, fault, n_docs)
    hbm("ingest")
    plan = loadgen.plan(mix, seed, seconds, cell.root)
    batch = int(cfg["service"]["max_batch"])
    warm = pool_q[np.arange(batch) % len(pool_q)]
    for _ in range(2):
        svc.search_batch(warm)
    if plan.loop == "open":
        svc.start_async()
        for f in [svc.search_async(q) for q in warm[:8]]:
            f.result(timeout=600)
    hbm("warm-up")
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace else (
        lambda name: contextlib.nullcontext())
    before = counters(svc, counter)
    cap = tracing.capture(tdir) if trace else contextlib.nullcontext()
    # Python's cyclic collector stops every thread of the process while it
    # walks the heap; the window runs without it, with what set-up left
    # frozen out of its sight.
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        with cap:
            with span(tracing.WINDOW_SPAN):
                if plan.loop == "closed":
                    win = run_closed(svc, pool_q, plan, seconds, span)
                else:
                    win = run_open(svc, pool_q, plan, seconds, span)
    finally:
        gc.enable()
        gc.unfreeze()
    after = counters(svc, counter)
    delta = {k: after[k] - before[k] for k in after}
    peak = memory_peak()
    hbm("window")
    if plan.loop == "open":
        svc.stop_async()
    answered = int(np.isfinite(win.done).sum())
    late = (win.sent - win.due)[np.isfinite(win.sent)] * 1e3
    log(f"window {win.seconds:.3f}s: {len(win.picks)} requests, {answered} answered, "
        f"{int(win.shed.sum())} shed, {int(win.errors.sum())} raised; generator lateness "
        f"p50 {np.percentile(late, 50) if late.size else float('nan'):.3f} ms "
        f"p95 {np.percentile(late, 95) if late.size else float('nan'):.3f} ms; counters {delta}")

    events = None
    if trace:
        events = tracing.load_events(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    kind = devices[0].device_kind
    peaks = registry.peaks(kind, cell.root) if devices[0].platform == "tpu" else {}
    ctx = MetricContext(cell=cell, events=events, counters=delta, peaks=peaks)

    # The program's state goes before the reference runs on the device.
    del svc
    gc.collect()
    hbm("program freed")
    corpus = jax.device_put(corpus, devices[0])

    chk = cfg["check"]
    answered_picks = np.unique(win.picks[np.isfinite(win.done)])
    sample = _sample(seed, answered_picks, int(chk["sample"]))
    answers = check.Answers(picks=win.picks, ids=win.ids, scores=win.scores)
    t0 = time.perf_counter()
    st, smp = check.prepare(registry.reference_module(cell), corpus, cfg["encoding"]["args"],
                            int(chk["block"]), pool_q, sample)
    k = int(cfg["service"]["k"])
    numbers = check.compare(answers, st, smp, k, int(cfg["service"]["depth"]),
                            int(chk["q_chunk"]), log=log)
    # Admitted requests whose answer never came (or raised) are wrong.
    numbers["invalid"] += float((~np.isfinite(win.done) & ~win.shed).sum())
    t1 = time.perf_counter()
    recall = check.recall_at_k(answers, check.exact_truth(st, pool_q, answered_picks, k), k)
    log(f"reference check over {len(sample)} of {len(answered_picks)} answered queries "
        f"{t1 - t0:.2f}s; exact top-{k} and recall over all of them "
        f"{time.perf_counter() - t1:.2f}s")

    limits = cfg["limits"]
    correct = answered > 0 and all(numbers[k] <= limits[k] for k in limits)
    metrics = per_layer(cell, ctx) if trace else end_to_end(cell, win, setup_s, recall)
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(len(win.picks)),
        "failed": int(win.shed.sum() + win.errors.sum()
                      + (~np.isfinite(win.done) & ~win.shed & ~win.errors).sum()),
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform, "kind": kind, "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if trace:
        result["device"]["busy_s"] = tracing.busy_ns(events) / 1e9
        lo, hi = tracing.window(events)
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": tracing.top_ops(events), "idle_gaps": tracing.idle_gaps(events),
        }
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    if recall is not None:
        log(f"recall_at_10 {recall:.6f}")
    return result


def _sample(seed: int, picks: np.ndarray, size: int) -> np.ndarray:
    """The queries whose answers are compared, drawn from the seed."""
    rng = np.random.default_rng([seed, 3])
    return np.sort(rng.choice(picks, size=min(size, len(picks)), replace=False))


def run_control(cell: registry.Cell, seed: int, corpus: np.ndarray, pool_q: np.ndarray,
                devices) -> Dict[str, Any]:
    """The precision control in the program's place (``check.control_answers``)
    over the same sample of queries a run compares, judged the same way."""
    import jax

    cfg, chk = cell.config, cell.config["check"]
    k, depth = int(cfg["service"]["k"]), int(cfg["service"]["depth"])
    sample = _sample(seed, np.arange(len(pool_q)), int(chk["sample"]))
    corpus_dev = jax.device_put(corpus, devices[0])
    st, smp = check.prepare(registry.reference_module(cell), corpus_dev, cfg["encoding"]["args"],
                            int(chk["block"]), pool_q, sample)
    answers = check.control_answers(st, smp, k, depth, int(chk["q_chunk"]))
    numbers = check.compare(answers, st, smp, k, depth, int(chk["q_chunk"]), log=log)
    recall = check.recall_at_k(answers, check.exact_truth(st, pool_q, sample, k), k)
    limits = cfg["limits"]
    return {
        "correct": all(numbers[n] <= limits[n] for n in limits),
        "attempted": len(sample), "failed": 0,
        "metrics": {} if recall is None else {"recall_at_10": {"value": recall, "unit": "fraction"}},
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak()},
        "check": {n: {"value": numbers[n], "limit": limits[n]} for n in limits},
    }


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int) -> None:
    """Exit non-zero unless JAX sees at least ``n`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"[bench] needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < n:
        raise SystemExit(f"[bench] the cell needs {n} chips; JAX found {len(devs)}")


def emit(result: Dict[str, Any]) -> None:
    """The result line last on stdout; the compared numbers last on stderr."""
    print(json.dumps(result), flush=True)
    for k, v in result["check"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr, flush=True)


def main(argv: List[str], t_start: float) -> int:
    args = parse(argv)
    cell = registry.resolve(args.workload)
    require_chips(cell.chips)
    from repro.kernels import common

    if common.INTERPRET or not common.USE_KERNEL_DEFAULT:
        raise SystemExit("[bench] the Pallas kernels are not compiled for this device")
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    emit(result)
    return 0
