"""A count module for a hybrid text tower in which one layer of every five is
softmax attention through the fused kernels and the other four are not: it
counts ``depth // 5`` applications a caption where ``flops.py`` counts
``depth``. tests/test_scopes.py."""

import types

import flops  # benchmark/ is on sys.path

train_flops_per_pair = flops.train_flops_per_pair
PERIOD = 5


def attention_least_s(cfg, tower, sequences, peaks):
    if tower == "textual":
        attention_layers = types.SimpleNamespace(**{**vars(cfg.text), "depth": cfg.text.depth // PERIOD})
        cfg = types.SimpleNamespace(vision=cfg.vision, text=attention_layers)
    return flops.attention_least_s(cfg, tower, sequences, peaks)
