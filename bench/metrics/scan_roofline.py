"""Device scan: share of the HBM roofline.  The least time the scans of the
queries answered in the traced window could take, their useful bytes
(``roofline.scan_bytes``: the code rows each query scores, from
``QueryStats.candidates_scanned``, and one lookup table per query) over
the chip's peak HBM bandwidth, divided by the device time of the window's
programs.  Bound by bytes: the scan does one table lookup and one add per
code byte."""

import roofline


def read(run):
    if (run.trace is None or run.peaks is None or not run.trace.program_s
            or not run.n_in_window):
        return None
    useful = roofline.scan_bytes(
        [a.stats.candidates_scanned for a in run.answers_in_window],
        run.config["pq_m"], run.config["pq_nbits"])
    least_s = useful / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / sum(run.trace.program_s.values())
