"""CNN convolutions: device milliseconds per round of the ops in the
model's `cnn.conv` scope, the convolutions with their bias adds and
their backward ops, in training and eval alike; self time averaged
over the chips (bench/model_scopes.py)."""
from bench import model_scopes


def read(ctx):
    return model_scopes.per_round_ms(ctx, "cnn.conv")
