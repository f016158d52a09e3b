"""Useful FLOPs of the window's answered steps per second, over the chip's
bf16 peak (%). A step's FLOPs are ``bench.costs.step_cost``'s: the work its
input routes to, whatever the program implements, as the mean over one
cycle of the program's inputs. Steps that answered nothing do not count."""


def read(rec):
    flops = sum(rec.costs[t.model][0] for t in rec.answered_in_window())
    if not flops:
        return None
    return 100.0 * flops / rec.seconds / rec.peaks["bf16_flops_per_s"]
