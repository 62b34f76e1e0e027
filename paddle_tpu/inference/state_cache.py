"""Recurrent-state cache: the second kind of per-sequence state a hybrid
model keeps, beside the paged KV pool.

A state-space layer keeps no keys and values: its whole past is one
fixed-size state per sequence — the selective recurrence's
``[heads, P, N]`` matrix in float32 and the last ``K - 1`` inputs of the
causal convolution.  So there is nothing to page: the cache is one row
per sequence slot, in pools of one array per RUN of consecutive
recurrent layers (the executor scans over a run, and the pool is the
scan's carry):

    ssm[r]   [layers of run r, slots, G, N, k * P]  float32
             (packed as ``ops/pallas_kernels/ssm_decode.py`` describes)
    conv[r]  [layers of run r, K - 1, slots, conv_dim]  the engine dtype

The rules a slot's row lives by (the executor keeps them):

- zero at a request's first token — taken IN the prefill program
  (``start == 0`` reads zeros), so a fresh or reused slot needs no reset
  and no stale state can leak;
- carried from prefill chunk to prefill chunk: the chunk program reads
  the slot's row and returns the row after the chunk, which
  :meth:`write` puts back with ONE donated device program per chunk;
- untouched, bit for bit, while other slots decode (the decode program
  writes live slots only);
- dropped on ``free``: nothing to do, the next owner starts from zero;
  a preempted request is rebuilt by recompute from its ``resume_ids``.

The pools have one owner, this object.  The decode program and the
writer take them donated (:meth:`pools`) and their outputs replace them
at once (:meth:`set_pools`).  They are allocated when the cache is built
(4.9 GB at 64 slots of granite-4.0-h-micro), so a size the device cannot
hold fails there and not at the first request.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs as _obs
from ..analysis import CountedJit


def _write_slot(ssm, conv, new_ssm, new_conv, slot):
    """Put one slot's rows into every pool (pools donated: in place)."""
    ssm = tuple(jax.lax.dynamic_update_slice_in_dim(
        pool, new[:, None], slot, axis=1)
        for pool, new in zip(ssm, new_ssm))
    conv = tuple(jax.lax.dynamic_update_slice_in_dim(
        pool, new[:, :, None].astype(pool.dtype), slot, axis=2)
        for pool, new in zip(conv, new_conv))
    return ssm, conv


class RecurrentStateCache:
    def __init__(self, runs, max_seqs, ssm_shape, conv_shape, dtype):
        """``runs``: the number of layers in each run of consecutive
        recurrent layers; ``ssm_shape``: one sequence's packed state in
        one layer; ``conv_shape``: ``(K - 1, conv_dim)``."""
        self.runs = tuple(int(n) for n in runs)
        self.max_seqs = int(max_seqs)
        self.ssm_shape = tuple(ssm_shape)
        self.conv_shape = tuple(conv_shape)
        self.dtype = jnp.dtype(dtype)
        self._ssm = tuple(jnp.zeros(self.ssm_pool_shape(r), jnp.float32)
                          for r in range(len(self.runs)))
        self._conv = tuple(jnp.zeros(self.conv_pool_shape(r), self.dtype)
                           for r in range(len(self.runs)))
        self._active = [False] * self.max_seqs
        #: the donated per-chunk writer
        self.writer = CountedJit(_write_slot, name="serve.state_write",
                                 donate_argnums=(0, 1))

    # -- sizes ---------------------------------------------------------------

    @staticmethod
    def bytes_for(runs, max_seqs, ssm_shape, conv_shape, dtype) -> int:
        """Bytes the pools of a cache built from these arguments hold."""
        per_layer = (4 * math.prod(ssm_shape)
                     + jnp.dtype(dtype).itemsize * math.prod(conv_shape))
        return sum(runs) * int(max_seqs) * per_layer

    @property
    def nbytes(self) -> int:
        """Bytes of state the pools hold."""
        return self.bytes_for(self.runs, self.max_seqs, self.ssm_shape,
                              self.conv_shape, self.dtype)

    @property
    def slots_used(self) -> int:
        return sum(self._active)

    def ssm_pool_shape(self, run):
        return (self.runs[run], self.max_seqs) + self.ssm_shape

    def conv_pool_shape(self, run):
        k, c = self.conv_shape
        return (self.runs[run], k, self.max_seqs, c)

    # -- control plane -------------------------------------------------------

    def allocate(self, slot: int) -> None:
        """A slot gets an owner.  Its row is NOT reset: the owner's first
        prefill chunk starts from zero in-graph."""
        self._active[slot] = True
        _obs.instant("state.alloc", cat="serve", slot=int(slot))

    def free(self, slot: int) -> None:
        self._active[slot] = False
        _obs.instant("state.free", cat="serve", slot=int(slot))

    # -- data plane ----------------------------------------------------------

    def pools(self):
        """``(ssm pools, conv pools)``, a tuple of arrays each, for a
        program that takes them (donated or not).  A donated array is
        deleted: keep none across a write."""
        return self._ssm, self._conv

    def set_pools(self, ssm, conv) -> None:
        self._ssm, self._conv = tuple(ssm), tuple(conv)

    def write(self, slot: int, new_ssm, new_conv, tokens: int) -> None:
        """The slot's rows after a prefill chunk of ``tokens`` tokens, per
        run ``[layers, G, N, kP]`` and ``[layers, K - 1, conv_dim]``: one
        dispatch whatever the number of layers."""
        before = self.writer.dispatches
        with _obs.span("state.write", cat="serve", slot=int(slot),
                       tokens=int(tokens)) as sp:
            self.set_pools(*self.writer(*self.pools(), tuple(new_ssm),
                                        tuple(new_conv), np.int32(slot)))
            sp.set(dispatches=self.writer.dispatches - before)
