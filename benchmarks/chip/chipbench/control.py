"""The control: the program's own outputs carried at the next precision below
the one the configuration states.

The configuration states exact arithmetic on 30-bit RNS residues held in
uint32 words.  The nearest precision below is float32, whose 24-bit
significand cannot hold such a residue; a kernel that ran its modular
products through float32 would round every word the way ``float32_words``
does.  The comparison that decides ``correct`` must fail on it.
"""

from __future__ import annotations


def float32_words(ct):
    """``ct`` with each c0/c1 residue rounded through float32."""
    import dataclasses

    import jax.numpy as jnp

    r = lambda x: jnp.asarray(x, jnp.uint32).astype(jnp.float32).astype(jnp.uint32)
    return dataclasses.replace(ct, c0=r(ct.c0), c1=r(ct.c1))
