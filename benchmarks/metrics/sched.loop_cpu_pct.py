"""CPU the generation loop's thread used, as a share of the window: the
change of ``app_tpu_loop_cpu_seconds_total`` (utime + stime of the
thread's /proc stat, read by the watchdog every 50 ms). Beside ``sched.host_busy_pct``, which
is wall time outside wait, fetch and park, the difference is time the
thread waited for the interpreter lock or for a core."""
from benchmarks.metrics._stall import window_pct


def read(ctx):
    return window_pct(ctx, "app_tpu_loop_cpu_seconds_total")
