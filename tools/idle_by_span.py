"""The device's idle time laid over the program's own spans, in one run.

    python3 tools/idle_by_span.py --workload <cell> --seed <n> [--cpu-rehearsal]

On the chip (``chiprun``).  A child process — the parent stays off the
chip and keeps its memory for the trace — trains a benchmark cell's
conf (``benchmarks/run.build_conf``: its configuration and its traffic,
the program's own initialisation) for nine rounds through the CLI with
``telemetry = 1``, the two tracers switched on in turn:

* rounds 1-2 with ``trace_dir`` on (the ``obs`` spans; the window closes
  itself after ``trace_steps`` steps), rounds 3-4 with nothing on;
* one ``profile = 1`` session of the program's own (``TraceController``)
  from the last chunk of round 5 to the first dispatch of round 7: two
  round boundaries, round 6 whole; round 8 with nothing on again.

Then the parent reads the session's ``.xplane.pb`` into
``benchmarks/lib/tracered``'s table of rows — the rows ``load_events``
makes, but of the lines this tool reads only (the device's ``XLA Ops``
and ``XLA Modules``, the loop's ``train.*``): all of a 32-step GoogLeNet
session's rows at once ran a 40 GiB machine out of memory (PR 38) — and
with ``tracered.device_planes`` / ``busy_intervals`` as they stand

* every idle gap of the device over 1 ms is laid over the union of the
  ``train.*`` spans of the round loop's thread (what of it no span
  covers is printed, in microseconds);
* the device's idle time between each step program and the next is
  laid over ``train.device_wait``, and at a round's head split on the
  DEVICE trace's clock into what lies before ``train.boundary`` (the
  loop noticing the last fence), under it, under ``train.head``, and
  after the head's end (the exposed tail of the upload), beside the
  ``boundary``, ``head`` and exposed-tail readings of the same round
  from the host clock (the telemetry record: ``run_exposed`` less its
  steps at the round's ``run`` per step, and at the programs' own rate
  in the session);
* the round loop's ``run`` per step is held against the mean duration
  per step of the ``XLA Modules`` events of the same rounds;
* the chunk period per step of every round is printed by what was on.

The report goes to stdout and ``chiprun_out/idle_by_span/<cell>.json``.
Without a TPU it exits 2; ``--cpu-rehearsal`` walks the same flow at toy
sizes (there is no device plane on the CPU: the trace's part is skipped)
and exits 3.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROUNDS = 9
GAP_NS = 1_000_000  # idle gaps over 1 ms


def overlap(a0, a1, b0, b1) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def covered(span, union) -> int:
    """Nanoseconds of ``span`` under a sorted union of intervals."""
    return sum(overlap(span[0], span[1], s, e) for s, e in union)


def say(msg: str) -> None:
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"[idle_by_span, peak {rss:.1f} GiB] {msg}", flush=True)


def read_trace(path: str):
    """One pass over the ``.xplane.pb``: ``tracered``'s rows (``[plane,
    line, name, start_ns, dur_ns]``) of the device planes' ``XLA Ops``
    (their names left out: only their intervals are read) and ``XLA
    Modules``; ``{name: [(start_ns, end_ns, stats)]}`` of the ``train.*``
    events of the round loop's thread, the host line that holds
    ``train.round``; and the number of events by plane and line."""
    from jax.profiler import ProfileData

    from benchmarks.lib import tracered

    rows, loop, counts = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        pname = plane.name
        device = pname.startswith("/device:TPU:")
        for line in plane.lines:
            lname = line.name
            n, spans = 0, {}
            keep = device and lname in (tracered.OPS_LINE,
                                        tracered.MODULES_LINE)
            for ev in line.events:
                n += 1
                if keep:
                    rows.append([pname, lname,
                                 "" if lname == tracered.OPS_LINE else ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)])
                elif not device and ev.duration_ns > 0:
                    name = ev.name
                    if name.startswith("train."):
                        spans.setdefault(name, []).append(
                            (int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns),
                             dict(ev.stats)))
            counts.setdefault(pname, {})[lname] = n
            if "train.round" in spans or "train.boundary" in spans:
                loop = {k: sorted(v, key=lambda x: x[0])
                        for k, v in spans.items()}
    return rows, loop, counts


def per_step(rec, stage):
    st = rec["stages"].get(stage, {})
    return st.get("total_s", 0.0), st.get("rows", 0)


def host_clock(rec, batch):
    """A round's ``boundary``, ``head``, device step and exposed tail
    from its telemetry record, in ms (``None`` where not billed)."""
    run_s, run_rows = per_step(rec, "run")
    exp_s, exp_rows = per_step(rec, "run_exposed")
    step = run_s / (run_rows / batch) if run_rows else None
    out = {"round": rec["round"],
           "boundary_ms": 1e3 * per_step(rec, "boundary")[0],
           "head_ms": 1e3 * per_step(rec, "head")[0],
           "run_ms_step": None if step is None else 1e3 * step,
           "chunk_ms_step": 1e3 * per_step(rec, "chunk")[0] / rec["steps"],
           "counters": {k: v for k, v in rec.get("counters", {}).items()
                        if k.startswith("chunks_")}}
    out["h2d_tail_ms"] = (
        1e3 * (exp_s - exp_rows / batch * step)
        if step is not None and exp_rows else None)
    return out


def analyse(rows, spans, records, batch: int, steps_round: int,
            scan: int) -> dict:
    """``rows`` and ``spans``: :func:`read_trace`'s; ``records``: the
    run's telemetry records; ``scan``: the steps of a chunk."""
    from benchmarks.lib import tracered

    planes = tracered.device_planes(rows)
    if not planes:  # a CPU: no device plane to lay the spans over
        return {"device": None,
                "span_counts": {k: len(v) for k, v in spans.items()}}
    busy = tracered.busy_intervals(rows, planes[0])
    union = tracered._union([(s, e) for v in spans.values()
                             for s, e, _ in v])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] > GAP_NS]
    bare = [{"gap_ms": (g[1] - g[0]) / 1e6,
             "uncovered_us": ((g[1] - g[0]) - covered(g, union)) / 1e3}
            for g in gaps]
    by_round = {r["round"]: host_clock(r, batch) for r in records}
    mods = sorted((r[3], r[3] + r[4]) for r in rows
                  if r[0] == planes[0] and r[1] == tracered.MODULES_LINE)
    whole = max((e - s for s, e in mods), default=0)
    progs = [m for m in mods if m[1] - m[0] > whole / 2]  # step programs
    module_ms_step = (sum(e - s for s, e in progs) / len(progs) / scan / 1e6
                      if progs else None)

    def under(pieces, name):
        return sum(covered(p, [(s, e) for s, e, _ in spans.get(name, [])])
                   for p in pieces) / 1e6

    # the device's idle time between one step program and the next (what
    # small programs run in it, the rng split of a dispatch, is busy),
    # laid over the loop's spans; with a ``train.head`` in it, it is the
    # gap at a round's head
    between = []
    for (_, a1), (b0, _) in zip(progs, progs[1:]):
        pieces = [(max(a1, e0), min(b0, s1))
                  for (_, e0), (s1, _) in zip(busy, busy[1:])
                  if s1 > a1 and e0 < b0 and min(b0, s1) > max(a1, e0)]
        row = {"idle_ms": sum(e - s for s, e in pieces) / 1e6,
               "under_device_wait_ms": under(pieces, "train.device_wait")}
        head = next(((h0, h1, st) for h0, h1, st in spans.get(
            "train.head", []) if a1 <= h0 and h1 <= b0 + 10 * GAP_NS), None)
        if head is not None:
            h0, h1, stats = head
            bound = [x for x in spans.get("train.boundary", [])
                     if x[1] <= h0]
            rnd = int(stats.get("step", 0)) // steps_round
            host = by_round.get(rnd)
            row.update({
                "head_of_round": rnd,
                "before_boundary_ms": sum(
                    overlap(s, e, a1, bound[-1][0]) for s, e in pieces) / 1e6
                if bound else None,
                "under_boundary_ms": under(pieces, "train.boundary"),
                "under_head_ms": under(pieces, "train.head"),
                "after_head_ms": sum(overlap(s, e, h1, b0)
                                     for s, e in pieces) / 1e6,
                "host_clock": host})
            if host and module_ms_step:
                rec = next(r for r in records if r["round"] == rnd)
                exp_s, exp_rows = per_step(rec, "run_exposed")
                # the exposed run less its steps at the programs' own
                # rate in this session
                row["host_tail_at_module_rate_ms"] = (
                    1e3 * exp_s - exp_rows / batch * module_ms_step)
        between.append(row)
    rounds = []
    for r0, r1, stats in spans.get("train.round", []):
        inside = [e - s for s, e in progs if r0 <= s and e <= r1]
        rec = by_round.get(int(stats.get("round", 0)) - 1)
        if inside and rec and rec["run_ms_step"]:
            mod = sum(inside) / len(inside) / scan / 1e6
            rounds.append({"round": rec["round"], "modules": len(inside),
                           "module_ms_step": mod,
                           "run_ms_step": rec["run_ms_step"],
                           "gap_pct": 100 * (rec["run_ms_step"] / mod - 1)})
    return {"device": planes[0],
            "session_ms": (busy[-1][1] - busy[0][0]) / 1e6,
            "busy_ms": sum(e - s for s, e in busy) / 1e6,
            "gaps_over_1ms": len(gaps),
            "gaps_uncovered_max_us": max(
                (g["uncovered_us"] for g in bare), default=0.0),
            "gaps": sorted(bare, key=lambda g: -g["gap_ms"])[:12],
            "span_counts": {k: len(v) for k, v in spans.items()},
            "step_programs": len(progs), "module_ms_step": module_ms_step,
            "between_programs": between, "run_against_modules": rounds}


def train(a) -> int:
    """The child: build the cell's conf, switch the tracers on in turn,
    train; leaves ``run.json`` beside the telemetry and the trace."""
    from benchmarks import run as bench

    spec = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = bench.find_cell(spec, a.workload)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = bench.load_json(os.path.join(ROOT, entry["file"]))
    traffic = bench.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    import jax

    platform = jax.devices()[0].platform
    if not a.cpu_rehearsal and platform != "tpu":
        sys.stderr.write(f"needs a TPU, found {platform!r}\n")
        return 2
    from cxxnet_tpu.utils import compile_cache

    compile_cache.enable()
    tele = os.path.join(a.out, "telemetry.jsonl")
    if os.path.exists(tele):
        os.remove(tele)
    conf = bench.build_conf(config, traffic, bench.fold_seed(a.seed), a.out,
                            a.cpu_rehearsal)
    steps_round = conf["scan"] * conf["chunks_per_round"]
    with open(conf["path"], "a", encoding="utf-8") as f:
        f.write(
            f"num_round = {ROUNDS}\nmax_round = {ROUNDS}\nsilent = 0\n"
            f"trace_dir = {os.path.join(a.out, 'host_trace')}\n"
            f"trace_steps = {3 * steps_round}\n"
            f"profile = 1\nprofile_dir = {os.path.join(a.out, 'profile')}\n"
            f"profile_start = {5 * steps_round + 2 * conf['scan']}\n"
            f"profile_steps = {steps_round + conf['scan']}\n")
    from cxxnet_tpu.cli import LearnTask

    rc = LearnTask().run([conf["path"]])
    if rc != 0:
        sys.stderr.write(f"the CLI returned {rc}\n")
        return 1
    with open(os.path.join(a.out, "run.json"), "w", encoding="utf-8") as f:
        json.dump({"platform": platform,
                   "device_kind": jax.devices()[0].device_kind,
                   "batch": conf["batch"], "scan": conf["scan"],
                   "steps_round": steps_round}, f)
    say("trained")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--train", action="store_true",
                    help="the child's half: train, and read nothing")
    a = ap.parse_args(argv)
    a.out = os.path.join(ROOT, "bench_out", "idle_by_span", a.workload)
    os.makedirs(a.out, exist_ok=True)
    if a.train:
        return train(a)
    rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                         "--train"] + (argv or sys.argv[1:])).returncode
    if rc != 0:
        return rc
    with open(os.path.join(a.out, "run.json"), encoding="utf-8") as f:
        ran = json.load(f)
    with open(os.path.join(a.out, "telemetry.jsonl"), encoding="utf-8") as f:
        records = [json.loads(x) for x in f if x.strip()]
    mode = {1: "trace_dir", 2: "trace_dir", 3: "off", 4: "off",
            6: "profile", 8: "off"}
    report = dict(
        ran, workload=a.workload, seed=a.seed,
        rounds=[dict(host_clock(r, ran["batch"]),
                     tracing=mode.get(r["round"], "mixed"))
                for r in records])
    from benchmarks.lib import tracered

    # a trace too large to read fails here, not the machine
    resource.setrlimit(resource.RLIMIT_AS, (24 * 2 ** 30, 24 * 2 ** 30))
    xplane = tracered.find_xplane(os.path.join(a.out, "profile"))
    say(f"{xplane}: {os.path.getsize(xplane) / 2 ** 20:.1f} MiB")
    rows, spans, counts = read_trace(xplane)
    say(f"{len(rows)} device rows of "
        f"{sum(n for c in counts.values() for n in c.values())} events")
    report["events_by_line"] = counts
    report["trace"] = analyse(rows, spans, records, ran["batch"],
                              ran["steps_round"], ran["scan"])
    dest = os.path.join(ROOT, "chiprun_out", "idle_by_span")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, a.workload + ".json"), "w",
              encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report, indent=1), flush=True)
    return 3 if a.cpu_rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
