"""Peak bytes on the fullest of the cell's chips, as the backend's
``memory_stats()`` reports them right after the window, in MB of 10**6
bytes: ``peak_bytes_in_use`` (buffers: parameters, optimizer state, batches)
plus ``peak_bytes_reserved`` (the TPU runtime keeps a program's temporaries
in a reservation of their own, which stays while the program is loaded; a
diagnostic on one v5e, PR 23, showed a program's 1,610.6 MB of temporaries
under ``bytes_reserved`` and none of it under ``peak_bytes_in_use``).
Guards "the batch that fitted still fits".
"""

METRIC = {
    "name": "peak_hbm_mb",
    "unit": "MB",
    "better": "lower",
    "source": "host_clock",
}


def read(run):
    return run["peak_bytes"] / 1e6 if run["peak_bytes"] else None
