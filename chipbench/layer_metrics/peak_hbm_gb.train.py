"""peak_hbm_gb.train (GB, 1e9 bytes): the device's peak as the line reports
it under ``memory_peak_bytes``: the allocator's ``peak_bytes_in_use`` (live
arrays) + ``peak_bytes_reserved`` (the temporaries of loaded programs)."""


def read(evidence):
    b = evidence.get("memory_peak_bytes")
    return b / 1e9 if b else None
