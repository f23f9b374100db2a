"""Device self time per step under the program's `ssm_core` scope (models/mixers.py SsmMixer, ops/ssm.py): the state-space recurrence alone, from x, B, C, dt, A, D to y: the chunk's decays, the intra-chunk scores and values, the chunk states, their scan and the states' outputs, forward, remat's forward and backward. benchmark/scopes_nemotron.py."""

META = {
    "name": "ssm_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "state-space mixer", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.per_step_ms(ctx, scopes_nemotron.SSM_CORE)
