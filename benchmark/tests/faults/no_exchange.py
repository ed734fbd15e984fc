"""Planted fault ``no_exchange``: the exchange between chips left out.  The
mesh's sharded expansion (``parallel/mesh.py`` ``seg_expand_packed_step``)
combines the shards' slots and segment counts with ``pmin`` and ``psum``
over the mesh; here both hand back what the one shard has, so an answer
holds only the edges of the rows one chip owns — well-formed, HTTP 200, and
short.  A four-chip cell's comparison has to come out NOT correct."""


def install():
    import jax

    jax.lax.psum = lambda x, axis_name, **kw: x
    jax.lax.pmin = lambda x, axis_name, **kw: x
