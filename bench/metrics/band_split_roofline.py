"""Spectral band split (``kernels/dct.py``), share of its roofline in %:
the analysis and residual passes together.  A lane's split does the
analysis to ``m`` coefficients and the synthesis of the high band
(``4·m·S·d`` operations) and moves the feature in and the low and high
bands out at the configuration's dtype.  FreqCa runs one per full
lane-step."""
from bench import readings, work

EVENTS = ("_band_split_spectral_pallas",)


def read(run):
    m, pol = run.cell.model, run.cell.policy
    if pol["name"] != "freqca":
        return None
    bins = work.kept_bins(run.tokens, pol["rho"])
    return readings.kernel_roofline(run, EVENTS, lambda calls: work.band_split(
        run.full_lane_steps, run.tokens, m["d_model"], bins, m["dtype"]))
