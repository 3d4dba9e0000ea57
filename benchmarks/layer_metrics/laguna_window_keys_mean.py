"""Keys a query of a sliding layer saw, averaged over the positions of a learn
pass, the three sliding layers and the minibatch steps of the window's last
row's iteration (``attn/window_keys_mean``, counted from the mask the layer
applies): 384.25 over 1024 positions with a window of 512, where a full
layer's query sees 512.5. As ``attn_window_keys_mean`` reads it for
``ppo_lift_phi4flash_16x1024``."""

from benchmarks.harness import parts

NAME = "laguna_window_keys_mean"


def read(run):
    return parts.last_row(run, "attn/window_keys_mean")
