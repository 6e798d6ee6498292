"""Peak device memory in use over the run, in GiB (the runtime's
``peak_bytes_in_use`` on the fullest chip)."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2 ** 30
