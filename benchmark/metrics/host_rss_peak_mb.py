"""The process's peak resident set, ``ru_maxrss``, read at the window's
close and before the reference run, in MiB."""


def read(run):
    return run.rss_peak_mb or None
