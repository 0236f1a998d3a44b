"""Serving latency/throughput benchmark (closed-loop + open-loop).

Drives the serving engine (``cxxnet_tpu/serve``) in-process over a
synthetic MLP — no HTTP in the way, so the numbers isolate the
micro-batcher + compiled-predict-cache data path:

* **closed-loop**: C worker threads, each firing its next request the
  moment the previous one returns — measures saturated throughput and
  the batching speedup over a single sequential client (the ISSUE-2
  acceptance bar: >= 3x at concurrency 16);
* **open-loop**: requests arrive on a fixed-rate clock regardless of
  completions (the honest way to measure latency under load — a
  closed loop self-throttles and hides queueing collapse); reports
  achieved rate and p50/p95/p99 latency at each offered rate.
* **open-loop burst profile** (``--open-loop --burst``): a square-wave
  arrival schedule alternating ``--base-rate`` and ``--burst-rate``
  every ``--phase`` seconds, sustained for ``--duration`` seconds (or
  until ``--total-requests`` arrivals — the ROADMAP's >= 10^6-request
  story; a scaled-down one runs in the FLEET=1 tier-1 lane, the
  full-scale on-chip one is ROADMAP S10).  Reports sustained
  p50/p99 plus explicit shed (429) / expired (504) / error counts, so
  admission-control behavior under burst pressure is a first-class
  series.  ``--url`` points the same harness at a running HTTP front
  end (e.g. the serving fleet) instead of the in-process engine; the
  URL client keeps one keep-alive connection per worker thread, and
  ``--wire binary`` posts CXB1 frames (doc/serving.md "Binary wire
  protocol") instead of JSON.
* **wire A/B** (``--wire-ab``): JSON-vs-binary closed-loop throughput
  over real HTTP against an in-process server — interleaved best-of-2
  legs plus a bitwise score-parity check (the WIRE=1 lane's >= 1.5x
  acceptance bar and the ``wire_bench`` perf-guard series).

Prints one JSON document on stdout.

Usage::

    python tools/serve_bench.py [--model mnist_mlp] [--dev cpu]
        [--concurrency 16] [--requests 200] [--rows 1]
        [--max-batch 64] [--timeout-ms 2] [--open-rates 100,500]
        [--open-duration 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_engine(args, scheme: str = ""):
    """Engine + request tensor over the builder conf.  ``scheme``
    quantizes the trainer's kernels in place (per-channel int8 +
    folded rescale, ``nnet/quant.py``) before the engine wraps it —
    the quant leg of the A/B serves the SAME conf and seed at reduced
    precision, through the identical construction path."""
    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu import serve
    from cxxnet_tpu.models import MODEL_BUILDERS
    from cxxnet_tpu.nnet.trainer import NetTrainer

    conf = MODEL_BUILDERS[args.model](
        batch_size=args.max_batch, dev=args.dev
    )
    tr = NetTrainer()
    tr.set_params(cfgmod.parse_pairs(conf))
    tr.init_model()
    if scheme:
        from cxxnet_tpu.nnet import quant as nquant

        nquant.apply_plan(tr, nquant.build_plan(tr, scheme), scheme)
    eng = serve.Engine(
        trainer=tr,
        max_batch_size=args.max_batch,
        batch_timeout_ms=args.timeout_ms,
        queue_limit=max(1024, 4 * args.concurrency),
    )
    row = tuple(tr.net.input_node_shape(1)[1:])
    x = np.random.RandomState(0).rand(args.rows, *row).astype(np.float32)
    return eng, x


def closed_loop(eng, x, concurrency, requests):
    """Each of ``concurrency`` threads runs ``requests`` back-to-back."""
    lat = []
    lock = threading.Lock()

    def worker():
        mine = []
        for _ in range(requests):
            t0 = time.perf_counter()
            eng.predict(x)
            mine.append(time.perf_counter() - t0)
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat.sort()
    n = len(lat)
    return {
        "concurrency": concurrency,
        "requests": n,
        "wall_sec": wall,
        "req_per_sec": n / wall,
        "rows_per_sec": n * x.shape[0] / wall,
        "latency_ms": {
            "p50": lat[n // 2] * 1e3,
            "p95": lat[min(n - 1, int(n * 0.95))] * 1e3,
            "p99": lat[min(n - 1, int(n * 0.99))] * 1e3,
        },
    }


def open_loop(eng, x, rate, duration):
    """Fixed-rate arrivals for ``duration`` seconds; late completions
    still count — achieved < offered means the server cannot keep up."""
    from cxxnet_tpu import serve as _serve

    lat, errs = [], [0]
    lock = threading.Lock()
    threads = []

    def fire():
        t0 = time.perf_counter()
        try:
            eng.predict(x)
        except _serve.ServeError:
            with lock:
                errs[0] += 1
            return
        dt = time.perf_counter() - t0
        with lock:
            lat.append(dt)

    period = 1.0 / rate
    t_start = time.perf_counter()
    k = 0
    while True:
        t_next = t_start + k * period
        now = time.perf_counter()
        if now - t_start >= duration:
            break
        if t_next > now:
            time.sleep(t_next - now)
        th = threading.Thread(target=fire)
        th.start()
        threads.append(th)
        k += 1
    for th in threads:
        th.join()
    wall = time.perf_counter() - t_start
    lat.sort()
    n = len(lat)
    out = {
        "offered_req_per_sec": rate,
        "sent": k,
        "completed": n,
        "shed_or_error": errs[0],
        "achieved_req_per_sec": n / wall,
    }
    if n:
        out["latency_ms"] = {
            "p50": lat[n // 2] * 1e3,
            "p95": lat[min(n - 1, int(n * 0.95))] * 1e3,
            "p99": lat[min(n - 1, int(n * 0.99))] * 1e3,
        }
    return out


def open_loop_burst(fire, base_rate, burst_rate, phase_s, duration_s,
                    total_requests=0, clients=64, progress_s=0.0):
    """Square-wave open-loop driver: arrivals alternate between
    ``base_rate`` and ``burst_rate`` req/s every ``phase_s`` seconds.

    ``fire()`` executes one request and returns ``(outcome, dt)`` with
    outcome one of ``ok`` / ``shed`` (429) / ``expired`` (504) /
    ``error``.  A fixed pool of ``clients`` workers drains a bounded
    arrival queue, so arrivals are never blocked by completions; if the
    pool cannot keep up the queue overflows into ``client_drop``
    (reported — a silent cap would read as 'covered the offered load'
    when it didn't).  ``progress_s > 0`` streams running counts and
    p50/p99 to stderr every that-many seconds — the >= 10^6-request
    story's live telemetry."""
    import queue as _q

    lat = []
    counts = {"ok": 0, "shed": 0, "expired": 0, "error": 0,
              "client_drop": 0}
    lock = threading.Lock()
    work: "_q.Queue" = _q.Queue(maxsize=10000)

    def worker():
        while True:
            item = work.get()
            if item is None:
                return
            outcome, dt = fire()
            with lock:
                counts[outcome] = counts.get(outcome, 0) + 1
                if outcome == "ok":
                    lat.append(dt)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    t_next = t0
    t_report = t0 + progress_s
    sent = 0
    while True:
        now = time.perf_counter()
        elapsed = now - t0
        if progress_s > 0 and now >= t_report:
            t_report = now + progress_s
            with lock:
                snap = sorted(lat)
                done = dict(counts)
            n = len(snap)
            p50 = snap[n // 2] * 1e3 if n else float("nan")
            p99 = snap[min(n - 1, int(n * 0.99))] * 1e3 if n \
                else float("nan")
            print(f"burst[{elapsed:.0f}s] sent {sent} ok {n} "
                  f"shed {done['shed']} expired {done['expired']} "
                  f"err {done['error']} p50 {p50:.2f} ms "
                  f"p99 {p99:.2f} ms",
                  file=sys.stderr, flush=True)
        if total_requests and sent >= total_requests:
            break
        if not total_requests and elapsed >= duration_s:
            break
        if t_next > now:
            time.sleep(min(t_next - now, 0.01))
            continue
        try:
            work.put_nowait(1)
            sent += 1
        except _q.Full:
            with lock:
                counts["client_drop"] += 1
        in_burst = int(elapsed / phase_s) % 2 == 1
        rate = burst_rate if in_burst else base_rate
        t_next += 1.0 / max(rate, 1e-9)
        if t_next < now - 1.0:
            t_next = now  # don't unwind a deep arrival backlog forever
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat.sort()
    n = len(lat)
    out = {
        "base_rate": base_rate,
        "burst_rate": burst_rate,
        "phase_s": phase_s,
        "wall_sec": wall,
        "sent": sent,
        "completed": n,
        "shed": counts["shed"],
        "expired": counts["expired"],
        "errors": counts["error"],
        "client_drop": counts["client_drop"],
        "achieved_req_per_sec": n / wall if wall > 0 else 0.0,
    }
    if n:
        out["latency_ms"] = {
            "p50": lat[n // 2] * 1e3,
            "p95": lat[min(n - 1, int(n * 0.95))] * 1e3,
            "p99": lat[min(n - 1, int(n * 0.99))] * 1e3,
        }
    return out


def make_engine_fire(eng, x, deadline_ms=0.0):
    """Burst-driver fire() over the in-process engine."""
    from cxxnet_tpu import serve as _serve

    def fire():
        t0 = time.perf_counter()
        try:
            eng.predict(x, deadline_ms=deadline_ms or None)
        except _serve.ServeError as e:
            kind = ("shed" if e.http_status == 429
                    else "expired" if e.http_status == 504 else "error")
            return kind, time.perf_counter() - t0
        except Exception:  # noqa: BLE001 - counted, bench keeps going
            return "error", time.perf_counter() - t0
        return "ok", time.perf_counter() - t0

    return fire


def make_url_fire(url, x, deadline_ms=0.0, priority="", wire_fmt="json"):
    """fire() over a running HTTP front end (single engine or fleet
    router) — POST /predict per request on a **per-thread pooled
    keep-alive connection** (``http.client``), not a fresh socket per
    request: the old ``urlopen``-per-request client spent most of its
    budget on TCP setup and measured the connect path, not the server.

    ``wire_fmt="binary"`` posts one pre-encoded CXB1 frame per request
    (doc/serving.md "Binary wire protocol") instead of JSON.  A stale
    pooled connection (server restarted, idle timeout) gets one
    fresh-socket retry; /predict is idempotent."""
    import http.client
    import urllib.parse

    u = urllib.parse.urlsplit(url if "//" in url else "http://" + url)
    host = u.hostname or "127.0.0.1"
    port = u.port or 80
    path = u.path.rstrip("/") + "/predict"
    if wire_fmt == "binary":
        from cxxnet_tpu.serve import wire as _wire

        payload = bytes(_wire.encode_request(
            x, kind="predict", priority=priority or "interactive",
            deadline_ms=deadline_ms))
        ctype = _wire.CONTENT_TYPE
    else:
        body = {"data": x.tolist()}
        if deadline_ms:
            body["deadline_ms"] = deadline_ms
        if priority:
            body["priority"] = priority
        payload = json.dumps(body).encode("utf-8")
        ctype = "application/json"
    tls = threading.local()

    def fire():
        t0 = time.perf_counter()
        status = None
        for attempt in (0, 1):
            conn = getattr(tls, "conn", None)
            fresh = conn is None
            if fresh:
                conn = http.client.HTTPConnection(host, port, timeout=30)
                tls.conn = conn
            try:
                conn.request("POST", path, body=payload,
                             headers={"Content-Type": ctype})
                r = conn.getresponse()
                r.read()
                status = r.status
                if r.will_close:
                    conn.close()
                    tls.conn = None
                break
            except (http.client.HTTPException, OSError):
                conn.close()
                tls.conn = None
                if fresh or attempt:
                    return "error", time.perf_counter() - t0
        dt = time.perf_counter() - t0
        if status == 200:
            return "ok", dt
        if status == 429:
            return "shed", dt
        if status == 504:
            return "expired", dt
        return "error", dt

    return fire


def closed_loop_http(fire, concurrency, requests, rows):
    """Closed loop over a pooled HTTP fire(): each worker reuses ONE
    keep-alive connection for all its requests."""
    lat = []
    errs = [0]
    lock = threading.Lock()

    def worker():
        mine = []
        for _ in range(requests):
            outcome, dt = fire()
            if outcome == "ok":
                mine.append(dt)
            else:
                with lock:
                    errs[0] += 1
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat.sort()
    n = len(lat)
    out = {
        "concurrency": concurrency,
        "requests": n,
        "errors": errs[0],
        "wall_sec": wall,
        "req_per_sec": n / wall if wall > 0 else 0.0,
        "rows_per_sec": n * rows / wall if wall > 0 else 0.0,
    }
    if n:
        out["latency_ms"] = {
            "p50": lat[n // 2] * 1e3,
            "p95": lat[min(n - 1, int(n * 0.95))] * 1e3,
            "p99": lat[min(n - 1, int(n * 0.99))] * 1e3,
        }
    return out


def check_wire_parity(url, x):
    """One row batch through both planes: binary scores must be
    BITWISE equal to the JSON scores (tolist() of f32 round-trips
    through float64 repr exactly)."""
    import urllib.request

    from cxxnet_tpu.serve import wire as _wire

    base = url.rstrip("/")
    req = urllib.request.Request(
        base + "/predict",
        data=json.dumps({"data": x.tolist(), "raw": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        jscores = np.asarray(json.loads(r.read())["scores"], np.float32)
    req = urllib.request.Request(
        base + "/predict",
        data=bytes(_wire.encode_request(x, kind="scores")),
        headers={"Content-Type": _wire.CONTENT_TYPE})
    with urllib.request.urlopen(req, timeout=30) as r:
        _k, _rid, wscores = _wire.decode_response(r.read())
    return bool(np.asarray(wscores, np.float32).tobytes()
                == jscores.tobytes())


def run_wire_ab(args) -> dict:
    """JSON-vs-binary wire A/B over real HTTP (the WIRE=1 lane's
    measurement and the ``wire_bench`` perf-guard series): the engine
    behind its own stdlib server, pooled keep-alive clients on both
    formats, interleaved best-of-2 closed-loop legs — back to back, so
    machine-load drift hits both equally (the autotune discipline) —
    plus the bitwise score-parity bit."""
    from cxxnet_tpu import serve

    eng, x = build_engine(args)
    httpd = serve.make_server(eng, port=0)
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    url = f"http://127.0.0.1:{httpd.server_port}"
    fire_j = make_url_fire(url, x, wire_fmt="json")
    fire_b = make_url_fire(url, x, wire_fmt="binary")
    try:
        for _ in range(8):
            fire_j()
            fire_b()
        parity = check_wire_parity(url, x)
        half = max(8, args.requests // 2)
        j_runs, b_runs = [], []
        for _ in range(2):
            b_runs.append(closed_loop_http(
                fire_b, args.concurrency, half, x.shape[0]))
            j_runs.append(closed_loop_http(
                fire_j, args.concurrency, half, x.shape[0]))
        jbest = max(j_runs, key=lambda r: r["req_per_sec"])
        bbest = max(b_runs, key=lambda r: r["req_per_sec"])
    finally:
        httpd.shutdown()
        httpd.server_close()
        eng.close()
    return {
        "model": args.model,
        "dev": args.dev,
        "rows_per_request": args.rows,
        "max_batch_size": args.max_batch,
        "wire_ab": {
            "json": jbest,
            "binary": bbest,
            "speedup": (bbest["req_per_sec"] / jbest["req_per_sec"]
                        if jbest["req_per_sec"] > 0 else 0.0),
            "bitwise_equal_scores": parity,
        },
    }


def run_open_loop_burst(args) -> dict:
    """The ``--open-loop --burst`` entry: in-process engine by default,
    a running front end with ``--url``."""
    eng = None
    if args.url:
        row = [0.5] * 16
        x = np.asarray([row] * args.rows, np.float32)
        fire = make_url_fire(args.url, x, deadline_ms=args.deadline_ms,
                             wire_fmt=args.wire)
    else:
        eng, x = build_engine(args)
        for _ in range(8):
            eng.predict(x)
        fire = make_engine_fire(eng, x, deadline_ms=args.deadline_ms)
    burst = open_loop_burst(
        fire, args.base_rate, args.burst_rate, args.phase,
        args.open_duration, total_requests=args.total_requests,
        clients=args.clients, progress_s=args.progress_s)
    result = {
        "model": args.model,
        "dev": args.dev,
        "url": args.url or None,
        "rows_per_request": args.rows,
        "max_batch_size": args.max_batch,
        "open_loop_burst": burst,
    }
    if eng is not None:
        result["serving_stats"] = eng.snapshot_stats()
        eng.close()
    return result


def run_quant_ab(args) -> dict:
    """f32-vs-quantized serving A/B (the QUANT lane's measurement and
    the TPU-queue entry): interleaved closed-loop legs — best-of-2 per
    side, back to back, so machine-load drift hits both equally (the
    autotune discipline) — plus the weight-bytes identity both engines
    report."""
    from cxxnet_tpu.ops import quant as opsq

    eng_f, x = build_engine(args)
    eng_q, _ = build_engine(args, scheme=args.quant)
    for _ in range(8):
        eng_f.predict(x)
        eng_q.predict(x)
    half = max(8, args.requests // 2)
    f_runs, q_runs = [], []
    for _ in range(2):
        q_runs.append(closed_loop(eng_q, x, args.concurrency, half))
        f_runs.append(closed_loop(eng_f, x, args.concurrency, half))
    f32 = max(f_runs, key=lambda r: r["req_per_sec"])
    qnt = max(q_runs, key=lambda r: r["req_per_sec"])
    wb_f, _ = opsq.weight_bytes(eng_f.trainer.params)
    wb_q, wb_q32 = opsq.weight_bytes(eng_q.trainer.params)
    out = {
        "model": args.model,
        "dev": args.dev,
        "rows_per_request": args.rows,
        "max_batch_size": args.max_batch,
        "quant_ab": {
            "scheme": args.quant,
            "f32": f32,
            "quant": qnt,
            "speedup": (qnt["req_per_sec"] / f32["req_per_sec"]
                        if f32["req_per_sec"] > 0 else 0.0),
            "weight_bytes_f32": wb_f,
            "weight_bytes_quant": wb_q,
            "bytes_ratio": (wb_q32 / wb_q) if wb_q else 0.0,
        },
    }
    eng_f.close()
    eng_q.close()
    return out


def run_autotune(args) -> dict:
    """Bad-knobs recovery for the serve plane: start the micro-batcher
    at deliberately bad settings (batch 1, 1 ms window), drive
    closed-loop traffic while the self-tuning controller
    (``cxxnet_tpu/tune``) retunes it — with speculative bucket prewarm
    compiling each bigger bucket BEFORE it goes live — then re-measure
    cleanly and compare against the hand-tuned defaults.  The TUNE=1
    lane asserts ``recovery_ratio >= threshold``."""
    import threading as _thr

    from cxxnet_tpu.tune import KnobController, batcher_knobs

    # hand-tuned reference engine: the defaults (max-batch capacity,
    # 2 ms).  Built and warmed now, MEASURED at the end interleaved
    # with the tuned engine — measuring the two legs ~30 s apart made
    # the recovery ratio hostage to machine-load drift between the
    # windows (the same fix io_bench.run_autotune carries).
    hand_eng, x = build_engine(args)
    for _ in range(8):
        hand_eng.predict(x)

    # bad knobs + controller; a fresh engine so stats stay per-leg
    eng2, x = build_engine(args)
    eng2.set_max_batch_size(1, prewarm=False)
    eng2.set_batch_timeout_ms(1.0)
    for _ in range(8):
        eng2.predict(x)
    bad = closed_loop(eng2, x, args.concurrency,
                      max(8, args.requests // 8))
    ctrl = KnobController(
        lambda: float(eng2.stats.batch_rows), batcher_knobs(eng2),
        period_s=args.tune_period, band=args.tune_band,
        name="serve_bench", on_tick=eng2.prewarm_buckets,
    )
    stop_evt = _thr.Event()

    def _traffic():
        while not stop_evt.is_set():
            try:
                eng2.predict(x)
            except Exception:
                time.sleep(0.01)

    threads = [_thr.Thread(target=_traffic, daemon=True)
               for _ in range(args.concurrency)]
    ctrl.start()
    for t in threads:
        t.start()
    time.sleep(args.autotune_seconds)
    ctrl.stop()
    stop_evt.set()
    for t in threads:
        t.join(timeout=5.0)
    snap = ctrl.snapshot()
    # interleaved clean re-measures: tuned / hand / tuned / hand, back
    # to back, best-of per leg — drift hits both legs equally
    half = max(8, args.requests // 2)
    tuned_runs, hand_runs = [], []
    for _ in range(2):
        tuned_runs.append(closed_loop(eng2, x, args.concurrency, half))
        hand_runs.append(closed_loop(hand_eng, x, args.concurrency, half))
    final = max(tuned_runs, key=lambda r: r["req_per_sec"])
    hand = max(hand_runs, key=lambda r: r["req_per_sec"])
    stats = eng2.snapshot_stats()
    eng2.close()
    hand_eng.close()
    recovery = (final["req_per_sec"] / hand["req_per_sec"]
                if hand["req_per_sec"] > 0 else 0.0)
    threshold = args.recovery
    return {
        "model": args.model,
        "dev": args.dev,
        "rows_per_request": args.rows,
        "closed_loop": {"concurrent": final},
        "autotune": {
            "seconds": args.autotune_seconds,
            "period_s": args.tune_period,
            "band": args.tune_band,
            "initial": {"max_batch_size": 1, "batch_timeout_ms": 1.0,
                        "req_per_sec": bad["req_per_sec"],
                        "p50_ms": bad["latency_ms"]["p50"]},
            "hand": {"max_batch_size": args.max_batch,
                     "batch_timeout_ms": args.timeout_ms,
                     "req_per_sec": hand["req_per_sec"],
                     "p50_ms": hand["latency_ms"]["p50"]},
            "tuned": {"max_batch_size": snap["knobs"]["max_batch_size"],
                      "batch_timeout_ms":
                          snap["knobs"]["batch_timeout_ms"],
                      "req_per_sec": final["req_per_sec"],
                      "p50_ms": final["latency_ms"]["p50"]},
            "controller": snap,
            "recovery_ratio": recovery,
            "threshold": threshold,
            "ok": bool(recovery >= threshold),
        },
        "serving_stats": stats,
    }


def validate_autotune(doc: dict) -> None:
    """Schema check for the serve ``--autotune`` verdict (the TUNE=1
    lane's contract); raises ValueError on drift."""
    import math

    at = doc.get("autotune")
    if not isinstance(at, dict):
        raise ValueError("serve autotune report: missing autotune section")
    for key in ("initial", "hand", "tuned", "recovery_ratio",
                "threshold", "ok", "controller"):
        if key not in at:
            raise ValueError(f"serve autotune report: missing {key!r}")
    for leg in ("initial", "hand", "tuned"):
        for field in ("req_per_sec", "p50_ms"):
            v = at[leg].get(field)
            if not (isinstance(v, (int, float)) and math.isfinite(v)
                    and v > 0):
                raise ValueError(
                    f"serve autotune report: bad {leg}.{field} {v!r}")
    conc = doc.get("closed_loop", {}).get("concurrent", {})
    if "req_per_sec" not in conc:
        raise ValueError(
            "serve autotune report: closed_loop.concurrent missing")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="mnist_mlp")
    ap.add_argument("--dev", default=os.environ.get("BENCH_DEV", "cpu"))
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--requests", type=int, default=200,
                    help="closed-loop requests per thread")
    ap.add_argument("--rows", type=int, default=1,
                    help="rows per request")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--timeout-ms", type=float, default=2.0)
    ap.add_argument("--open-rates", default="",
                    help="comma-separated offered req/s for open-loop runs")
    ap.add_argument("--open-duration", type=float, default=3.0,
                    dest="open_duration",
                    help="seconds per open-loop run (and the burst "
                         "profile's total duration)")
    ap.add_argument("--duration", type=float, dest="open_duration",
                    default=argparse.SUPPRESS,
                    help="alias of --open-duration for the burst mode")
    ap.add_argument("--open-loop", action="store_true",
                    help="run the open-loop driver (with --burst: the "
                         "square-wave burst profile)")
    ap.add_argument("--burst", action="store_true",
                    help="burst profile: alternate --base-rate and "
                         "--burst-rate every --phase seconds")
    ap.add_argument("--base-rate", type=float, default=100.0)
    ap.add_argument("--burst-rate", type=float, default=400.0)
    ap.add_argument("--phase", type=float, default=1.0,
                    help="seconds per burst-profile phase")
    ap.add_argument("--total-requests", type=int, default=0,
                    help="stop after this many arrivals instead of "
                         "--duration (the >= 10^6-request story)")
    ap.add_argument("--clients", type=int, default=64,
                    help="burst-driver worker pool size")
    ap.add_argument("--progress-s", type=float, default=0.0,
                    help="stream running burst counts + p50/p99 to "
                         "stderr every N seconds (0 = off)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline for the burst driver")
    ap.add_argument("--url", default="",
                    help="drive a running HTTP front end (fleet router "
                         "or single server) instead of the in-process "
                         "engine")
    ap.add_argument("--wire", default="json",
                    choices=("json", "binary"),
                    help="wire format for the --url client (binary = "
                         "CXB1 frames, doc/serving.md)")
    ap.add_argument("--wire-ab", action="store_true",
                    help="JSON-vs-binary closed-loop A/B over HTTP "
                         "(WIRE=1 lane); exits 1 if the score parity "
                         "check fails")
    ap.add_argument("--json", dest="json_path", default="",
                    help="also write the JSON report here")
    ap.add_argument("--quant", default="",
                    help="run the f32-vs-quantized A/B at this scheme "
                         "(int8|bf16) instead of the plain bench")
    ap.add_argument("--autotune", action="store_true",
                    help="bad-knobs recovery via the tune controller "
                         "(TUNE=1 lane); exits 1 below --recovery")
    ap.add_argument("--autotune-seconds", type=float, default=15.0)
    ap.add_argument("--tune-period", type=float, default=0.5)
    ap.add_argument("--tune-band", type=float, default=0.1)
    ap.add_argument("--recovery", type=float, default=0.9,
                    help="autotune pass bar vs the hand-tuned rate")
    args = ap.parse_args(argv)

    if args.open_loop and args.burst:
        result = run_open_loop_burst(args)
        b = result["open_loop_burst"]
        print(json.dumps(result, indent=1))
        if args.json_path:
            with open(args.json_path, "w", encoding="utf-8") as f:
                json.dump(result, f, indent=1)
        lat = b.get("latency_ms", {})
        print(f"bench[burst:{args.model}] sent {b['sent']} "
              f"ok {b['completed']} shed {b['shed']} "
              f"expired {b['expired']} err {b['errors']} "
              f"achieved {b['achieved_req_per_sec']:.1f} req/s "
              f"p50 {lat.get('p50', float('nan')):.2f} ms "
              f"p99 {lat.get('p99', float('nan')):.2f} ms",
              file=sys.stderr, flush=True)
        return 0 if b["errors"] == 0 else 1

    if args.wire_ab:
        result = run_wire_ab(args)
        ab = result["wire_ab"]
        print(json.dumps(result, indent=1))
        if args.json_path:
            with open(args.json_path, "w", encoding="utf-8") as f:
                json.dump(result, f, indent=1)
        print(f"bench[wire_ab:{args.model}] json "
              f"{ab['json']['req_per_sec']:.1f} req/s vs binary "
              f"{ab['binary']['req_per_sec']:.1f} req/s speedup "
              f"{ab['speedup']:.3f} parity "
              f"{'ok' if ab['bitwise_equal_scores'] else 'FAIL'} "
              f"p99 {ab['json']['latency_ms']['p99']:.2f} -> "
              f"{ab['binary']['latency_ms']['p99']:.2f} ms",
              flush=True)
        return 0 if ab["bitwise_equal_scores"] else 1

    if args.quant:
        result = run_quant_ab(args)
        ab = result["quant_ab"]
        print(json.dumps(result, indent=1))
        if args.json_path:
            with open(args.json_path, "w", encoding="utf-8") as f:
                json.dump(result, f, indent=1)
        # one self-contained verdict line, greppable from a long log
        print(f"bench[quant_ab:{args.model}] f32 "
              f"{ab['f32']['req_per_sec']:.1f} req/s vs {ab['scheme']} "
              f"{ab['quant']['req_per_sec']:.1f} req/s speedup "
              f"{ab['speedup']:.3f} bytes_ratio {ab['bytes_ratio']:.2f} "
              f"p99 {ab['f32']['latency_ms']['p99']:.2f} -> "
              f"{ab['quant']['latency_ms']['p99']:.2f} ms",
              flush=True)
        return 0

    if args.autotune:
        result = run_autotune(args)
        validate_autotune(result)
        at = result["autotune"]
        print(json.dumps(result, indent=1))
        if args.json_path:
            with open(args.json_path, "w", encoding="utf-8") as f:
                json.dump(result, f, indent=1)
        print(f"# autotune: bad {at['initial']['req_per_sec']:.0f} req/s "
              f"-> tuned {at['tuned']['req_per_sec']:.0f} req/s "
              f"(batch={at['tuned']['max_batch_size']}, "
              f"timeout={at['tuned']['batch_timeout_ms']:.2f}ms) vs hand "
              f"{at['hand']['req_per_sec']:.0f} req/s: recovery "
              f"{at['recovery_ratio']:.2f} "
              f"({'OK' if at['ok'] else 'FAIL'} at >= {at['threshold']})",
              file=sys.stderr, flush=True)
        return 0 if at["ok"] else 1

    eng, x = build_engine(args)
    for _ in range(8):
        eng.predict(x)  # warm the bucket + compile

    seq = closed_loop(eng, x, concurrency=1, requests=args.requests)
    conc = closed_loop(eng, x, concurrency=args.concurrency,
                       requests=args.requests)
    result = {
        "model": args.model,
        "dev": args.dev,
        "rows_per_request": args.rows,
        "max_batch_size": args.max_batch,
        "batch_timeout_ms": args.timeout_ms,
        "closed_loop": {
            "sequential": seq,
            "concurrent": conc,
            "speedup": conc["req_per_sec"] / seq["req_per_sec"],
        },
    }
    rates = [float(r) for r in args.open_rates.split(",") if r.strip()]
    if rates:
        result["open_loop"] = [
            open_loop(eng, x, rate, args.open_duration) for rate in rates
        ]
    result["serving_stats"] = eng.snapshot_stats()
    eng.close()
    print(json.dumps(result, indent=1))
    if args.json_path:
        with open(args.json_path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
