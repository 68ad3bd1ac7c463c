"""Peak device memory in use over the chip's HBM, highest chip, in
percent (the runtime's ``peak_bytes_in_use``)."""


def read(run):
    if not run.memory_peak:
        return None
    return 100.0 * max(run.memory_peak) / run.peak["hbm_bytes"]
