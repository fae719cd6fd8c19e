"""The policy's share of the chip's bf16 peak in the training cells:
the Nature CNN operations the window's updates need, over the traced
window's time, at ``bf16_flops_per_s`` per chip.

Per update of ``T`` steps over ``N`` lanes the program needs one
forward pass per served frame (the rollout, ``T * N``), one per lane
for the bootstrap value (``N``), and per epoch a forward and a backward
pass over every frame (3 forward passes' worth, ``3 * epochs * T *
N``).  ``cnn_forward_flops`` counts one forward pass from the layers'
shapes (2 per multiply-add of each convolution and dense product);
bias adds, activations, the optimizer and everything else are left
out."""

CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))   # (channels, kernel, stride)
HIDDEN = 512


def cnn_forward_flops(obs_shape, num_actions: int) -> int:
    """Multiply-add operations x 2 of one Nature CNN forward pass over
    one ``(C, H, W)`` observation."""
    c, h, w = obs_shape
    flops = 0
    for out, k, s in CONVS:
        h, w = (h - k) // s + 1, (w - k) // s + 1
        flops += 2 * h * w * out * k * k * c
        c = out
    flops += 2 * h * w * c * HIDDEN
    flops += 2 * HIDDEN * (num_actions + 1)
    return flops


def update_flops(counts) -> int:
    frames = counts["num_steps"] * counts["num_envs"]
    passes = frames + counts["num_envs"] + 3 * counts["epochs"] * frames
    return passes * cnn_forward_flops(counts["obs_shape"],
                                      counts["num_actions"])


def read(trace, counts):
    if not counts.get("updates") or trace.window_s <= 0:
        return None
    chips = max(1, len(trace.ops))
    need = counts["updates"] * update_flops(counts)
    return 100.0 * need / (trace.window_s * chips
                           * counts["peaks"]["bf16_flops_per_s"])
