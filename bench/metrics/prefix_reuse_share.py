"""``prefix_reuse_share``: prompt tokens served from a cached prefix over
the prompt tokens of the requests sent in the window (%), from the
engine's own ``describe()`` counts."""
import readers


def read(run):
    return readers.share(run, "reused_tokens", "prompt_tokens")
