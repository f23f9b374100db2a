"""What the compiler says the step program needs on one chip at its fullest:
``compiled.memory_analysis().peak_memory_in_bytes``; GB = 1e9 bytes. It is the
field that crosses the chip's capacity where the compiler's refusal does (PERF.md
section 6, PR 22: so400m at microbatch 64 reads 16.07 GB and compiles, at 96 it
is refused at 15.83 of 15.75 GiB), while arguments + outputs + temporaries + code
- aliased already reads 17.56 GB for the step that fits."""

META = {
    "name": "peak_hbm_gb", "unit": "GB", "better": "lower", "source": "program_counter",
    "layer": "step builder", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    m = ctx["counters"]["memory_analysis"]
    if m is None:
        return None
    peak = getattr(m, "peak_memory_in_bytes", 0)
    if not peak:  # an older jaxlib: the sum, which overstates (buffers are reused)
        peak = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
                + m.generated_code_size_in_bytes - m.alias_size_in_bytes)
    return peak / 1e9
