"""Seconds JAX spent tracing programs to jaxprs and lowering them to
MLIR modules by the end of set-up: the program's compile listener
(``obs/device.py``), cumulative.  Beside ``xla_compile_s`` (the backend
compile or cache load) this is what a program costs before it reaches
the compiler, the telemetry's extra lowering for its cost analysis
included."""

LAYER = "compile cache"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    a = run["device_at_setup"]
    if not a or "trace_seconds" not in a or "lower_seconds" not in a:
        return None
    return float(a["trace_seconds"]) + float(a["lower_seconds"])
