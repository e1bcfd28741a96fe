"""Flash attention (``kernels/flash_attention.py``), share of its
roofline in %.  A call over B lanes does ``4·B·H·S²·hd`` operations (QKᵀ
and PV) and moves Q, K, V and O at the configuration's dtype.  Every
full lane-step makes its family's ``flash_calls`` calls over its
``attention_tokens`` (``bench/programs/<family>.py``), padded lanes
included, since the kernel computes them."""
from bench import readings, work

EVENTS = ("_flash",)


def read(run):
    m, prog = run.cell.model, run.program
    lanes = run.full_lane_steps * prog.flash_calls(m)
    return readings.kernel_roofline(run, EVENTS, lambda calls: work.flash(
        lanes, prog.attention_tokens(m, run.tokens), m["n_heads"],
        m["d_model"] // m["n_heads"], m["dtype"]))
