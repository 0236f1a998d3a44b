#!/usr/bin/env python
"""Closed-loop smoke: a real ``task=serve_train`` process, end to end.

Drives the full production loop the way an operator would (ISSUE 6
acceptance): launch ``python -m cxxnet_tpu <conf> task=serve_train``
against a freshly trained checkpoint, POST >= 1k feedback records over
HTTP in two phases — first deliberately POISONED labels (the eval gate
must reject the degraded candidate and the trainer must roll back),
then correct labels (the gate must publish and the engine must
hot-reload the new weights fingerprint) — and verify every claim from
the outside: the event log for ``loop.reject`` / ``loop.rollback`` /
``loop.publish``, ``/healthz`` for the served round + crc, ``/metricsz``
for the gauges.  Emits one JSON verdict line on stdout::

    {"ok": true, "records": 1256, "rejected": ..., "published": ...,
     "round_before": 1, "round_after": 2, "crc_changed": true, ...}

Wired into tier-1 as the opt-in ``LOOP=1`` lane (tools/run_tier1.sh).

Usage: python tools/loop_smoke.py [--out DIR] [--records N]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

CONF = """
data = train
iter = synthetic
  nsample = 256
  input_shape = 1,1,16
  nclass = 4
  seed_data = 1
iter = end
eval = heldout
iter = synthetic
  nsample = 256
  input_shape = 1,1,16
  nclass = 4
  seed_data = 1
iter = end

netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 16
  init_sigma = 0.1
layer[+1:a1] = relu:a1
layer[a1->out] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end

input_shape = 1,1,16
batch_size = 32
dev = cpu
eta = 0.05
metric = error
"""


def _post(port: int, path: str, obj: dict, timeout: float = 30.0) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(port: int, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        body = r.read()
    return json.loads(body) if path != "/metricsz" else body.decode()


def _events(path: str, kind: str):
    out = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if e.get("kind") == kind:
                    out.append(e)
    except OSError:
        pass
    return out


def _wait_for(predicate, what: str, timeout_s: float = 120.0,
              poll_s: float = 0.5):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        got = predicate()
        if got:
            return got
        time.sleep(poll_s)
    raise TimeoutError(f"timed out after {timeout_s:.0f}s waiting for {what}")


def _fail(msg: str, proc=None) -> None:
    if proc is not None:
        proc.kill()
        out = proc.stdout.read() if proc.stdout else ""
        sys.stderr.write(f"--- serve_train output ---\n{out}\n")
    print(json.dumps({"ok": False, "error": msg}), flush=True)
    raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="workdir (default: a fresh temp dir)")
    ap.add_argument("--records", type=int, default=1200,
                    help="total feedback records to ingest (>= 1000)")
    args = ap.parse_args()
    t_start = time.monotonic()
    work = args.out or tempfile.mkdtemp(prefix="loop_smoke_")
    os.makedirs(work, exist_ok=True)
    conf_path = os.path.join(work, "loop.conf")
    with open(conf_path, "w", encoding="utf-8") as f:
        f.write(CONF)
    mdir = os.path.join(work, "models")
    events_path = os.path.join(work, "events.jsonl")

    # ---- the initial serving checkpoint (one quick training round)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.io.data import create_iterator
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils import checkpoint as ckpt

    cfg = cfgmod.parse_pairs(CONF)
    split = cfgmod.split_sections(cfg)
    tr = NetTrainer()
    tr.set_params(split.global_entries)
    tr.set_param("seed", "0")
    tr.init_model()
    it = create_iterator(split.sections[0].entries)
    it.set_param("batch_size", "32")
    it.init()
    rows, labs = [], []
    while it.next():
        b = it.value()
        rows.append(np.asarray(b.data).copy())
        labs.append(np.asarray(b.label).copy())
        tr.update_all(b.data, b.label)
    X, Y = np.concatenate(rows), np.concatenate(labs)
    os.makedirs(mdir, exist_ok=True)
    ckpt.write_checkpoint(
        ckpt.publish_path(mdir, 1), tr.checkpoint_bytes(), round_=1,
        net_fp=tr.net_fp(),
    )

    # ---- launch the serve_train process
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "cxxnet_tpu", conf_path,
         "task=serve_train", f"model_dir={mdir}",
         f"loop_dir={os.path.join(work, 'loop')}",
         "serve_port=0", "loop_cycle_period_s=0.5",
         "loop_min_records=200", "loop_rounds_per_cycle=2",
         "loop_replay_ratio=0.25",
         f"event_log={events_path}", "silent=0"],
        env=env, cwd=work, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    port = None
    try:
        # the CLI prints the bound port on the ready line
        t0 = time.monotonic()
        for line in proc.stdout:
            sys.stderr.write(line)
            if "http://" in line:
                port = int(line.rsplit(":", 1)[1].split(";")[0]
                           .split("/")[0].strip())
                break
            if time.monotonic() - t0 > 180 or proc.poll() is not None:
                break
        if port is None:
            _fail("serve_train never reported a ready port", proc)
        # keep draining the child's stdout (verbose request logging
        # would fill the pipe and wedge the server otherwise)
        import threading

        threading.Thread(
            target=lambda: [None for _ in proc.stdout], daemon=True
        ).start()
        h0 = _get(port, "/healthz")
        round_before, crc_before = h0["round"], h0["model_crc32"]

        n_poison = args.records // 2
        n_correct = args.records - n_poison
        ingested = 0

        def post_rows(data, labels, chunk=32):
            nonlocal ingested
            for lo in range(0, data.shape[0], chunk):
                out = _post(port, "/feedback", {
                    "data": data[lo: lo + chunk].tolist(),
                    "label": labels[lo: lo + chunk].tolist(),
                })
                ingested += out["appended"]

        # ---- phase A: poisoned labels -> gate must reject + roll back
        idx = np.arange(n_poison) % X.shape[0]
        post_rows(X[idx], ((Y[idx] + 1.0) % 4))
        _wait_for(lambda: _events(events_path, "loop.reject"),
                  "the eval gate to reject the poisoned candidate")
        _wait_for(lambda: _events(events_path, "loop.rollback"),
                  "the trainer rollback event")
        h1 = _get(port, "/healthz")
        if h1["round"] != round_before:
            _fail(f"degraded candidate was served: round {h1['round']}",
                  proc)
        # every poisoned record consumed before the correct phase (the
        # publish must provably come from clean data)
        _wait_for(
            lambda: sum(c.get("records", 0)
                        for c in _events(events_path, "loop.cycle"))
            >= n_poison,
            "all poisoned records to be consumed")

        # ---- phase B: correct labels -> gate must publish + hot-reload
        idx = np.arange(n_correct) % X.shape[0]
        post_rows(X[idx], Y[idx])
        publishes = _wait_for(
            lambda: _events(events_path, "loop.publish"),
            "the eval gate to publish the improving candidate")
        _wait_for(lambda: _get(port, "/healthz")["round"] > round_before,
                  "the engine to hot-reload the published round")
        # loop.cycle is emitted after loop.publish: let the trained
        # cycles' own records land before the verdict counts them
        _wait_for(lambda: len(_events(events_path, "loop.cycle")) >= 2,
                  "both trained cycles' records")
        h2 = _get(port, "/healthz")
        mez = _get(port, "/metricsz")
        for needle in (f"serve_model_round {h2['round']}",
                       "loop_feedback_records_total",
                       'loop_publish_total{decision="published"}',
                       'loop_publish_total{decision="rejected"}'):
            if needle not in mez:
                _fail(f"/metricsz is missing {needle!r}", proc)

        # lineage: the publish pointer must name the id range that
        # trained the served model, and obs_dump --lineage must resolve
        # it back to committed feedback pages (ISSUE 7 acceptance)
        import obs_dump

        lineage_report, lineage_problems = obs_dump.resolve_lineage(
            mdir, os.path.join(work, "loop", "feedback"))
        lin = lineage_report.get("lineage") or {}
        resolved = lineage_report.get("resolved") or {}
        lineage_ok = (not lineage_problems
                      and isinstance(lin.get("first_seq"), int)
                      and isinstance(lin.get("last_seq"), int)
                      and lin.get("records", 0) >= 1
                      and resolved.get("records_in_range", 0) >= 1)

        verdict = {
            "ok": True,
            "records": ingested,
            "lineage": lin or None,
            "lineage_resolved": lineage_ok,
            "rejected": len(_events(events_path, "loop.reject")),
            "rollbacks": len(_events(events_path, "loop.rollback")),
            "published": len(publishes),
            "cycles": len(_events(events_path, "loop.cycle")),
            "round_before": round_before,
            "round_after": h2["round"],
            "crc_changed": h2["model_crc32"] != crc_before,
            "gain": publishes[-1].get("gain"),
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
        ok = (verdict["records"] >= 1000 and verdict["rejected"] >= 1
              and verdict["rollbacks"] >= 1 and verdict["published"] >= 1
              and verdict["cycles"] >= 2
              and verdict["round_after"] > verdict["round_before"]
              and verdict["crc_changed"] and verdict["lineage_resolved"])
        verdict["ok"] = bool(ok)
        # ---- graceful drain
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        verdict["exit_code"] = rc
        verdict["ok"] = verdict["ok"] and rc == 0
        print(json.dumps(verdict), flush=True)
        raise SystemExit(0 if verdict["ok"] else 1)
    finally:
        if proc.poll() is None:
            proc.kill()


if __name__ == "__main__":
    main()
