"""Peak bytes in use on the fullest chip (memory_stats), in GB."""

import jax


def read(run):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:run["cell"]["chips"]]]
    peaks = [p for p in peaks if p]
    return max(peaks) / 1e9 if peaks else None
