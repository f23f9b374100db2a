"""The part of the collective time per step during which no other operation runs on that device: what the collectives cost the step."""

META = {
    "name": "collective_exposed_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "loss island and gradient sync", "moves": "pairs_per_s_per_chip", "workloads": ['b16-bs256-dp4'],
}


def read(ctx):
    d = ctx["trace"]["device"]
    return 1e3 * d["collective_exposed_s_per_step"] if d["steps"] else None
