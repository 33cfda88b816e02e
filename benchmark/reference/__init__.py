"""The plain reference: the T2OMCA mathematics in ``jax.numpy`` / float32,
importing nothing of the program (``t2omca_tpu``). ``model`` — agent and
mixer; ``qmix`` — episode loss, gradients, clipping, Adam; ``env`` — the
deterministic part of the MEC-offloading transition; ``replay`` — ring
bookkeeping and proportional PER."""
