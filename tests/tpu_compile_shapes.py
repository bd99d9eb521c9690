"""What both compile test files for a described TPU v5e use beside their
fixtures (``topo``, ``one_chip``, ``on_tpu``: ``conftest.py``)."""

import jax


def _spec(tree, sharding):
    """The pytree with every array leaf replaced by its shape on the
    described device (there is no device to hold an array)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)
