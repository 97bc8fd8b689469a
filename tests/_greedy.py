"""The serving tests' oracle: a request's greedy decode by ``generate``
alone."""
import jax.numpy as jnp
import numpy as np

from elephas_tpu.models.transformer import generate

#: a prompt is right-padded to a multiple of this many tokens, its length
#: passed beside it, and decoded to the end of the sequence: greedy
#: decoding's first ``n`` tokens do not depend on how many follow, so the
#: references of a file compile once a width, not once a (prompt length,
#: budget) pair
WIDTH_STEP = 16


def greedy_reference(params, config, prompt, n):
    """The ``n`` tokens that greedy ``generate`` appends to ``prompt``."""
    prompt = np.asarray(prompt, np.int32)
    padded = -(-len(prompt) // WIDTH_STEP) * WIDTH_STEP
    width = max(len(prompt), min(padded, config.max_seq_len - n))
    row = np.zeros((1, width), np.int32)
    row[0, :len(prompt)] = prompt
    out = generate(params, jnp.asarray(row), config.max_seq_len - width,
                   config, prompt_lengths=[len(prompt)])
    return list(np.asarray(out)[0, :n])
