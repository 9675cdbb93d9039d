"""Peak device memory after the window, fullest chip, as the allocator saw
it: buffers in use plus what it reserved for running programs' temporaries
(`harness.memory_peaks`)."""


def read(run):
    peaks = run.counters.get("memory_peaks")
    return max(peaks) / 1e9 if peaks else None
