"""peak_bytes_in_use of the fullest device, read after the window."""


def read(run):
    return float(max(run.peak_bytes)) if run.peak_bytes else None
