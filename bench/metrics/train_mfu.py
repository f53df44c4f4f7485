"""Device: local-training matmul operations the window's applies needed,
over the window times the chip's peak (%)."""


def read(run):
    if run.peaks is None or not run.n:
        return None
    return 100.0 * run.train_flops() / (run.window_s * run.peaks["flops_per_s"])
