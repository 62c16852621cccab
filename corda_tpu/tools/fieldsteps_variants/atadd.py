# A variant file for `fieldsteps.py --variant`: executed with F = corda_tpu.ops.field after import.
# This one puts back what PR 32 took out: a product's rows placed by slice update (`.at[].add`, a
# scatter-add a row) instead of `pad` + `add`. Chip-free, F.mul goes from 12 to 61 fusions; on a TPU v5e,
# with the fold placed the same way, from 11.1 to 52.3 us (PERF.md section 6, PR 32).
def add(self, row, riv, off):
    grow = off + len(riv) - len(self.lo)
    if grow > 0:
        self.lo += [0] * grow
        self.hi += [0] * grow
        if self.v is not None:
            self.v = F._pad_to(self.v, len(self.lo))
    if self.v is None:
        self.v = jnp.zeros(row.shape[:-1] + (len(self.lo),), jnp.int32)
    shape = jnp.broadcast_shapes(self.v.shape[:-1], row.shape[:-1])
    self.v = jnp.broadcast_to(self.v, shape + self.v.shape[-1:])
    self.v = self.v.at[..., off:off + len(riv)].add(row)
    for j, (l, h) in enumerate(riv):
        self.lo[off + j] += l
        self.hi[off + j] += h


F._Columns.add = add    # noqa: F821 (F, jnp, jax, np are the names the tool executes this file with)
