"""The selected read of the latent cache against the floor of the model's
own work (``pbench.dsa.read_work``): the window's ``attended_keys`` (query,
selected key) pairs x 128 heads x 2 operations x (192 + 128) where the pair is
a prompt's (the expanded form: a chunk's queries share a key's expansion) or
x (576 + 512) where it is a decode step's (the absorbed form), whose 576-value
bfloat16 row also has to come from the cache (1,152 bytes): whichever peak is
slower, over the own device time under ``dstpu.attn.latent``. ISSUE 43 wrote
the absorbed count for every pair; a perfect expanded-form kernel would read
340 % of that, so it is no floor for a prompt (perfbench/DSA.md). The floor
counts the pairs the model attends and not the keys an implementation touches,
so a read that scores every causal key and masks reads low."""
from pbench import dsa


def read(v):
    return dsa.latent_roofline(v)
