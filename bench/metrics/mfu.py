"""Model FLOP utilisation of the whole step, in %: the useful operations
of every image completed in the traced window, over the window's length
times the chips times the bf16 peak.  Useful means real lanes only: each
image's full steps at the analytic cost of a forward by its family's
count (``forward_flops`` of ``bench/programs/<family>.py``) plus the band
split that fills a FreqCa cache, and its cached steps at the analytic
cost of the reconstruction and final layer; padded lanes count
nothing."""
from bench import work


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    model, policy = run.cell.model, run.cell.policy
    n_steps = run.cell.engine["n_steps"]
    forward = run.program.forward_flops(model, run.tokens)
    flops = sum(work.image_flops(forward, model, run.tokens, policy,
                                 a.result.n_full_steps, n_steps)
                for a in run.completed())
    if not flops:
        return None
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peak["bf16_flops_per_s"])
