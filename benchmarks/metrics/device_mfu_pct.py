"""The step program's share of the chip's bf16 peak WHILE IT RUNS:
FLOPs of one forward+backward step counted from the conf's shapes
(``lib/netconf.step_flops``) / ``device_step_ms`` / peak.  Not an
end-to-end utilisation: the time the device waits for the host is not
in it (``device_idle_pct`` has that)."""

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"


def read(run):
    t = run["trace"]
    if not t or not t["steps"] or t["busy_s"] <= 0 or not run["peaks"]:
        return None
    step_s = t["busy_s"] / t["steps"]
    flops = run["flops_per_step"] / run["chips"]
    return 100.0 * flops / step_s / run["peaks"]["bf16_flops"]
