"""Observability smoke: short telemetry train + serve scrape, end to end.

The driver behind the ``OBS=1`` lane of ``tools/run_tier1.sh``
(doc/observability.md).  One process:

1. generates a tiny synthetic MNIST-style dataset and trains it for a
   couple of scanned rounds (``scan_steps = 2``: the round loop's own
   fences bill ``head`` / ``run`` / ``run_exposed`` / ``boundary`` and
   feed ``train_step_device_seconds``) with ``telemetry=1``,
   ``event_log``, ``trace_dir`` and a deliberately-tripped ``alert=``
   rule armed — producing ``telemetry.jsonl`` (with per-round
   ``device`` totals), ``events.jsonl`` and a Chrome host trace;
2. serves the checkpoint it just wrote (``serve/`` engine + HTTP
   front-end), drives a few ``/predict`` requests through the
   micro-batcher, walks the latency alert through fire (degraded
   ``/healthz``) and clear, and scrapes ``GET /metricsz`` /
   ``GET /alertz`` to ``<out>/metricsz.txt`` / ``<out>/alertz.json``;
3. prints the artifact paths — the lane then schema-validates them via
   ``tools/obs_dump.py --check`` (including the device-plane metric
   families pinned with ``--require``).

Usage:  python tools/obs_smoke.py --out /tmp/obs_smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONF_TEMPLATE = """
data = train
iter = mnist
  path_img = "{out}/data/tr-img.idx"
  path_label = "{out}/data/tr-lab.idx"
  shuffle = 1
iter = end
eval = test
iter = mnist
  path_img = "{out}/data/te-img.idx"
  path_label = "{out}/data/te-lab.idx"
iter = end

netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.1
layer[+1:sg1] = relu
layer[sg1->fc2] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end

input_shape = 1,1,64
batch_size = 64
dev = cpu
save_model = 1
num_round = 2
eval_train = 1
eta = 0.3
metric = error
model_dir = {out}/models
telemetry = 1
telemetry_path = {out}/telemetry.jsonl
event_log = {out}/events.jsonl
trace_dir = {out}/traces
trace_steps = 3
scan_steps = 2
alert = smoke_latency:serve_request_latency_seconds_mean:>:0:0
silent = 1
"""


def make_data(out: str) -> None:
    from cxxnet_tpu.io.mnist import write_idx_images, write_idx_labels

    rng = np.random.RandomState(0)
    n, hw = 256, 8
    imgs = rng.randint(0, 256, (n, hw, hw)).astype(np.uint8)
    flat = imgs.reshape(n, -1).astype(np.float32)
    labels = (np.argsort(np.argsort(flat.mean(1))) * 4 // n).astype(np.uint8)
    os.makedirs(os.path.join(out, "data"), exist_ok=True)
    write_idx_images(os.path.join(out, "data", "tr-img.idx"), imgs)
    write_idx_labels(os.path.join(out, "data", "tr-lab.idx"), labels)
    write_idx_images(os.path.join(out, "data", "te-img.idx"), imgs[:64])
    write_idx_labels(os.path.join(out, "data", "te-lab.idx"), labels[:64])


def train(out: str) -> None:
    from cxxnet_tpu.cli import LearnTask

    conf = os.path.join(out, "smoke.conf")
    with open(conf, "w", encoding="utf-8") as f:
        f.write(CONF_TEMPLATE.format(out=out))
    rc = LearnTask().run([conf])
    if rc != 0:
        raise SystemExit(f"obs_smoke: train failed with rc={rc}")


def serve_and_scrape(out: str) -> None:
    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.obs import alerts as obs_alerts
    from cxxnet_tpu.serve import Engine
    from cxxnet_tpu.serve.server import make_server

    with open(os.path.join(out, "smoke.conf"), "r", encoding="utf-8") as f:
        cfg = cfgmod.split_sections(cfgmod.parse_pairs(f.read()))
    engine = Engine(cfg=cfg.global_entries,
                    model_dir=os.path.join(out, "models"),
                    max_batch_size=8, batch_timeout_ms=2.0)
    httpd = make_server(engine, port=0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    port = httpd.server_port

    def get(path: str):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            ctype = r.headers.get("Content-Type", "")
            body = r.read().decode("utf-8")
        return ctype, body

    try:
        # drive the evaluator by hand for determinism (the CLI started
        # its background thread — its passes would race the fire/clear
        # assertions below)
        ev = obs_alerts.evaluator()
        ev.stop()
        # baseline evaluation BEFORE traffic: the latency rule keys on
        # the interval mean, so the next pass sees fresh observations
        ev.evaluate_once()
        rng = np.random.RandomState(1)
        for n in (1, 3, 5):
            body = json.dumps(
                {"data": rng.randn(n, 64).astype(float).tolist()}
            ).encode("utf-8")
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/predict", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                out_rows = len(json.load(r)["pred"])
                assert out_rows == n, (out_rows, n)
        # fire: requests landed since the baseline pass, mean > 0
        ev.evaluate_once()
        if ev.firing() != ["smoke_latency"]:
            raise SystemExit(
                f"obs_smoke: latency alert did not fire ({ev.firing()})")
        _, health = get("/healthz")
        h = json.loads(health)
        if h["status"] != "degraded" or "smoke_latency" not in h.get(
                "alerts", []):
            raise SystemExit(f"obs_smoke: /healthz not degraded while "
                             f"firing: {h}")
        _, alertz = get("/alertz")  # captured while firing
        ctype, text = get("/metricsz")
        assert ctype.startswith("text/plain"), ctype
        # clear: no traffic between passes -> no interval mean sample
        ev.evaluate_once()
        if ev.firing():
            raise SystemExit(
                f"obs_smoke: alert did not clear ({ev.firing()})")
        h2 = json.loads(get("/healthz")[1])
        if h2["status"] != "ok":
            raise SystemExit(f"obs_smoke: /healthz stuck degraded: {h2}")
    finally:
        httpd.shutdown()
        httpd.server_close()
        engine.close()
    # the acceptance surface: outcomes, batch fill, latency, reloads,
    # the alert gauge and the device-plane families (the in-process
    # train + the serve bucket compiles above feed them)
    for needle in ("serve_request_outcomes_total", "serve_batch_rows_total",
                   "serve_request_latency_seconds_bucket",
                   "serve_model_reloads_total", "obs_events_total",
                   "obs_alerts_firing", "xla_program_compile_seconds",
                   "xla_compile_seconds_total"):
        if needle not in text:
            raise SystemExit(f"obs_smoke: {needle!r} missing from /metricsz")
    with open(os.path.join(out, "metricsz.txt"), "w",
              encoding="utf-8") as f:
        f.write(text)
    with open(os.path.join(out, "alertz.json"), "w",
              encoding="utf-8") as f:
        f.write(alertz)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="/tmp/obs_smoke",
                    help="artifact directory (created if missing)")
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    for leftover in ("telemetry.jsonl", "events.jsonl", "metricsz.txt",
                     "alertz.json"):
        p = os.path.join(out, leftover)
        if os.path.exists(p):
            os.remove(p)
    make_data(out)
    train(out)
    serve_and_scrape(out)
    traces = sorted(os.listdir(os.path.join(out, "traces")))
    print(f"obs_smoke: OK — artifacts in {out}")
    print(f"  metrics:   {out}/metricsz.txt")
    print(f"  alertz:    {out}/alertz.json")
    print(f"  telemetry: {out}/telemetry.jsonl")
    print(f"  events:    {out}/events.jsonl")
    print(f"  traces:    {traces}")


if __name__ == "__main__":
    main()
