"""Share of the window the PREDICT scans spent turning their chunk outputs
into results: the time of the program's ``scan.finalize`` spans (copies
to the host, concatenation, row and result-page assembly), over the
window."""
from bench import spans


def read(run):
    clipped = spans.window(run)
    if clipped is None:
        return None
    return 100.0 * spans.seconds(clipped, "scan.finalize") / run.window_s
