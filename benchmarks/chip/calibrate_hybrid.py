"""``calibrate.py`` for a hybrid cell, with one more planted fault:
``ssm_state_unchanged``, a decode step that hands back the SSM state it was
given (the rest of its cache moves on).

    python3 benchmarks/chip/calibrate_hybrid.py --workload <cell> \\
        --seconds <s> --seeds <n>... [--controls <k>] [--fault <name>]
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import calibrate  # noqa: E402


def stale_ssm_decode(orig):
    """A ``ContinuousBatcher._model_decode`` that runs ``orig`` and hands
    back the SSM state it was given with the rest of the new cache.  The
    state is copied first: a batcher that donates its cache to the decode
    step no longer holds the one it gave."""
    import jax
    import jax.numpy as jnp

    def is_ssm(path):
        return path[-1].key == "ssm"

    def decode(self, tok):
        kept = jax.tree_util.tree_map_with_path(
            lambda path, old: jnp.copy(old) if is_ssm(path) else None,
            self.cache)
        logits, cache = orig(self, tok)
        return logits, jax.tree_util.tree_map_with_path(
            lambda path, new, old: old if is_ssm(path) else new,
            cache, kept)

    return decode


def stale_ssm_state():
    from repro.training.serving import ContinuousBatcher

    ContinuousBatcher._model_decode = stale_ssm_decode(
        ContinuousBatcher._model_decode)


calibrate.FAULTS["ssm_state_unchanged"] = stale_ssm_state

if __name__ == "__main__":
    sys.exit(calibrate.main())
