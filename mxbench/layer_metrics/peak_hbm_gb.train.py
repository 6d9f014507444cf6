"""Device: peak bytes held on the fullest chip after the window, in GB
(1e9 bytes): live arrays plus the loaded programs' reserved temporaries
(``meters.peak_bytes``)."""
UNIT = "GB"


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
