"""Fused FreqCa cached step (``kernels/freqca_fused.py``), share of its
roofline in %.  A lane's reconstruction synthesises the low band from its
``m`` coefficients and adds the K-entry Hermite combination of the high
band: it reads the K history entries and the coefficients, writes the
predicted feature, and each call reads the synthesis basis, all at the
configuration's dtype.  FreqCa runs one per cached lane-step."""
from bench import readings, work

EVENTS = ("_freqca_predict_spectral_pallas",)


def read(run):
    m, pol = run.cell.model, run.cell.policy
    if pol["name"] != "freqca":
        return None
    bins = work.kept_bins(run.tokens, pol["rho"])
    lanes = run.total_lane_steps - run.full_lane_steps
    return readings.kernel_roofline(
        run, EVENTS, lambda calls: work.freqca_predict(
            lanes, run.tokens, m["d_model"], bins, work.cache_k(pol),
            m["dtype"], calls=calls))
