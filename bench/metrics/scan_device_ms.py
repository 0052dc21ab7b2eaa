"""Device scan (core/distributed, kernels/pq_adc, core/pq): device time of
the programs that ran in the traced window, per query answered in it.  On
the serving path the only device programs are those of the scan stage:
the lookup-table build and the window scan with its top-n."""


def read(run):
    if run.trace is None or not run.trace.program_s or not run.n_in_window:
        return None
    return 1e3 * sum(run.trace.program_s.values()) / run.n_in_window
