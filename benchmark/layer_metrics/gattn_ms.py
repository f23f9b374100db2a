"""Device self time per step under the flax path of a layer's attention (`textual/encoder/block<i>/attn/...`, models/transformer.py Attention) in a stack that mixes window and full layers: the five projections (q, k, v, gate, out), the head norms of q and k, the rotation of the window layers, the sigmoid gate and the core, forward, remat's forward and backward; nothing where no operation ran under `attn_gate`. benchmark/scopes_trinity.py."""

META = {
    "name": "gattn_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "gated window / full attention", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.gated_attention_ms(ctx)
