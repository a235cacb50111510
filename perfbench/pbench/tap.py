"""The serving engine with a tap on its logits, for the comparisons that
need rows and not tokens (``parity_phi4flash.py`` on the chip, the tier-1
tests of a model with slot state on the CPU). Nothing a timed run uses."""

import numpy as np


def tap_engine():
    """-> a subclass of ``InferenceEngineV2`` that remembers the logits
    each emitted token was sampled from: ``rows[uid]`` is a list of (V,)
    rows, one a token so far, greedy only.

    Every program samples through ``_sample_per_slot``; the tap keeps what
    that was handed and the two places that post tokens pick their rows
    out. On a mesh of several devices a callback can be neither ordered nor
    counted on to fire once, so each carries the order it was traced in,
    which within one program is the order it runs in."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    class TapEngine(InferenceEngineV2):
        def __init__(self, *a, **kw):
            self.taps, self.rows, self.fresh, self.traced = [], {}, {}, 0
            super().__init__(*a, **kw)

        def _sample_per_slot(self, logits, rng, temps, top_ks,
                             all_greedy=False):
            self.traced += 1
            jax.debug.callback(
                lambda x, tag=self.traced: self.fresh.setdefault(
                    tag, np.asarray(x)), logits)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def _drain(self):
            jax.effects_barrier()
            # the two readers look at the newest dispatch's taps only
            self.taps = (self.taps + [self.fresh[tag] for tag in
                                      sorted(self.fresh)])[-32:]
            self.fresh = {}

        def _post_token(self, seq, token):
            self._drain()
            if not seq.generated:
                # a prefill's or a last chunk's token: the newest 1-row tap
                one = [t for t in self.taps if t.shape[0] == 1][-1]
                self.rows[seq.uid] = [one[0]]
            super()._post_token(seq, token)

        def _post_decode_tokens(self, batch, toks):
            self._drain()
            slots = self.config.max_batch_size
            steps = [t for t in self.taps if t.shape[0] == slots][-len(toks):]
            for slot, uid in enumerate(self.state_mgr._slots):
                if uid is None or not batch.active[slot]:
                    continue
                seq = self.state_mgr.get_sequence(uid)
                left = seq.max_new_tokens - len(seq.generated)
                self.rows[uid] += [s[slot] for s in steps[:left]]
            return super()._post_decode_tokens(batch, toks)

    return TapEngine
