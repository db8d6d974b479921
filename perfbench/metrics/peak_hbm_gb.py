"""Peak bytes in use on the fullest chip
(memory_stats()['peak_bytes_in_use'] via engine_stats.device_memory)."""


def read(ctx):
    peaks = [int(m["peak_bytes_in_use"])
             for m in ctx.stats_end.get("device_memory", [])]
    return max(peaks) / 1e9 if peaks else None
