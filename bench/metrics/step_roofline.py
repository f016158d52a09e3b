"""The step programs' least time over their device time in the trace (%).

A model's least time per step is max(FLOPs / peak FLOP/s, bytes / peak
bytes/s) from ``bench.costs.step_cost``: the work the input routes to,
whatever the program implements, as the mean over one cycle of the
program's inputs. At batch 1 the bytes bound it."""


def read(rec):
    if rec.trace is None:
        return None
    least, spent = 0.0, 0.0
    for model, (runs, device_s) in rec.trace.step_runs.items():
        flops, nbytes = rec.costs[model]
        t = max(flops / rec.peaks["bf16_flops_per_s"], nbytes / rec.peaks["hbm_bytes_per_s"])
        least += runs * t
        spent += device_s
    if not spent:
        return None
    return 100.0 * least / spent
