"""Model step: device time per decode pass, in ms, of the operations
around the attention kernel that move the K/V cache (copies, slices and
update-slices of arrays with a cache-length and a key/value-head axis)."""


def read(run):
    return run.kv_copy_ms()
