"""A field of `device.memory_stats()` read after the window, on the fullest
chip; `scale` converts (1e-9: bytes -> GB).  None where the backend reports
none (the CPU rehearsal)."""


def read(spec, run):
    value = run["memory_stats"].get(spec["key"])
    return None if not value else value * spec.get("scale", 1.0)
