"""memory_stats()['peak_bytes_in_use'], the fullest device.

In `rf3-query-thin` the fullest of the four devices."""



def read(m):
    return max(m.peak_bytes) if m.peak_bytes else None
