"""The plain float32 reference of ``decoder.py`` for weights held in
bfloat16.

The benchmark hands the reference the arrays it served, which a
bfloat16 configuration holds in bfloat16.  ``decoder.py`` computes in
the dtype its residual stream starts in, the embedding table's, so here
the table enters in float32: every later operation then promotes the
stored weights to float32, and the whole pass runs in float32 at
``highest`` precision on exactly the weights the program served.  Only
the table is converted (0.3 GB at Granite-3.0's widths), not the stack.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.reference import decoder


def gaps(c: Dict, w, prompt, served, *, control: bool = False
         ) -> np.ndarray:
    """``decoder.gaps`` with the embedding table in float32."""
    import jax.numpy as jnp
    emb = w["embed"]["embedding"].astype(jnp.float32)
    w = dict(w, embed=dict(w["embed"], embedding=emb))
    return decoder.gaps(c, w, prompt, served, control=control)
