"""The runtime's own account of the most the fullest chip held while the job ran:
``bytes_in_use`` (live arrays) + ``bytes_reserved`` (what the loaded step sets aside
for its temporaries) of ``memory_stats()``, sampled by the job after its warm-up and
after its window (``harness.MemoryWatch``), or the high-water mark of live arrays
where that is larger; GB = 1e9 bytes. The same number as ``device.memory_peak_bytes``;
``peak_hbm_gb`` is the compiler's figure for the step."""

META = {
    "name": "hbm_live_peak_gb", "unit": "GB", "better": "lower", "source": "program_counter",
    "layer": "device", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
