"""100 x ``compile_cache.hits`` / ``compile_cache.requests`` of the program's compile log: the share of the compiles that asked the persistent cache and found their program there. 0.0 where the cache is off or nothing compiled."""

import setup_record

META = {
    "name": "compile_cache_hit_pct", "unit": "%", "better": "higher", "source": "program_counter",
    "layer": "runtime boundary", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    rec = setup_record.record(ctx)
    if rec is None:
        return None
    requests = rec.counters.get("compile_cache.requests", 0)
    return 100.0 * rec.counters.get("compile_cache.hits", 0) / requests if requests else 0.0
