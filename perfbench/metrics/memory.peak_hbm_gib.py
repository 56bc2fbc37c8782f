"""Peak bytes in use on the fullest chip (``memory_stats()``) after the
window, in GiB."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2**30
