"""Device: peak device memory in use over the memory the device offers
(``peak_bytes_in_use / bytes_limit`` of ``memory_stats()``)."""


def read(run):
    m = run.memory
    if not m or not m.get("bytes_limit"):
        return None
    return m["peak_bytes_in_use"] / m["bytes_limit"]
