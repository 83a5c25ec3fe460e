"""``dbl_apply_flat2d`` (kernels/dbl_merge.py) against its roofline: the
least time of its calls in the window, from the bytes and operations of a
``(rows, 128)`` f32 store update (``bench/cost/kernels.py``), over the
summed device time of its events.  Memory bound: 3 f32 arrays of the
store per call against 2 operations per element."""
from bench.cost import kernels

# the trace names the kernel's op by its HLO text: a call whose result is
# the f32 (rows, 128) store and which holds a Pallas (tpu_custom_call)
PATTERN = r"^%\S+ = f32\[\d+,128\]\S* .*tpu_custom_call"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.op_seconds(PATTERN)
    calls = run.trace.op_count(PATTERN)
    if seconds <= 0 or calls == 0:
        return None
    flops, nbytes = kernels.dbl_apply_flat2d(run.out["flat_rows"])
    least = calls * max(flops / run.peak["bf16_flops"],
                        nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
