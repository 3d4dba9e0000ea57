"""Keys a windowed query saw, averaged over the positions of a learn pass
and the minibatch steps of the window's last row's iteration
(``attn/window_keys_mean``, counted from the mask the window layer
applies): 384.25 over 1024 positions with a window of 512, and 512.5 if the
window were ignored."""

from benchmarks.harness import parts

NAME = "attn_window_keys_mean"


def read(run):
    return parts.last_row(run, "attn/window_keys_mean")
