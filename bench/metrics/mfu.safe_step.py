"""mfu.safe_step: model FLOPs of a train step over the chips' bf16 peak for
the step program's device time, %.

``step_flops`` counts what the model needs for one step over all the
learners' tokens T (sequences of S tokens, L layers, h heads of hd):

    6 N T + 6 L T S h hd

6 N T is the forward (2 N T) and backward (4 N T) of the matrix products,
N the parameters that multiply an activation: each layer's attention and
MLP matrices and the output head (not the embedding's lookup, not the
norms). The second term is causal attention: Q K^T and P V each take
2 S hd operations a token and head at full width, half of that under the
causal mask, so 2 S h hd a token a layer forward and three times that
with the backward. Recomputation (remat) is not counted. The time is the
step program (``jit_train_step``) on the ``XLA Modules`` line, per step
and per chip; the peak is ``bf16_flops_per_s`` times the chips. Moves
``round_s``.
"""


def step_flops(c: dict) -> float:
    """Model FLOPs of one step, from the driver's counts ``c``."""
    d, f, L = c["hidden_size"], c["intermediate_size"], c["layers"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // heads
    per_layer = d * heads * hd + 2 * d * kv * hd + heads * hd * d + 3 * d * f
    n_matmul = L * per_layer + c["vocab_size"] * d
    T, S = c["tokens_per_step"], c["seq_len"]
    attention = 6 * L * T * S * heads * hd
    return 6.0 * n_matmul * T + attention


def read(t):
    seconds, runs = t.module_s("jit_train_step")
    if not runs:
        return None
    step_s = seconds / runs
    peak = t.peaks["bf16_flops_per_s"] * t.chips
    return 100.0 * step_flops(t.counts) / (step_s * peak)
