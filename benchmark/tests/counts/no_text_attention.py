"""A count module as a later configuration would bring it, for a text tower that
runs no softmax attention through the program's fused kernels (a stack of
state-space, convolution or linear-attention layers): ``attention_least_s`` is 0
for it, and ``attn_text_roofline_pct`` then reads nothing, whatever custom calls
the tower spends its time in. Everything else is ``flops.py``'s (the image
tower is a SigLIP one). tests/test_scopes.py."""

import flops  # benchmark/ is on sys.path

train_flops_per_pair = flops.train_flops_per_pair


def attention_least_s(cfg, tower, sequences, peaks):
    return 0.0 if tower == "textual" else flops.attention_least_s(cfg, tower, sequences, peaks)
