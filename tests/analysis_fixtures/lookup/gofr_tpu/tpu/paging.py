"""Fixture for the hotloop pass's program-lookup check: parsed by graftlint,
never imported."""

import jax
import jax.numpy as jnp
import numpy as np

from .engine import program_lookup  # the fixture tree has no engine.py: never imported


class Paged:
    @program_lookup
    def _prefill_program(self, bucket, K):
        jnp_ = self._jnp
        tokens = jnp_.zeros((K, bucket), dtype=jnp.int32)   # FLAG: an array
        lengths = jnp.ones((K,), dtype=jnp.int32)           # FLAG: an array
        key = jax.random.PRNGKey(0)                         # FLAG: an array
        return self.executor.compile("prefill", self._fn, (tokens, lengths,
                                                           key))

    @program_lookup
    def _decode_program(self, width):
        # no flag: shape and dtype, nothing on the device; numpy is the host's
        table = jax.ShapeDtypeStruct((self.n_slots, width), jnp.dtype("int32"))
        host = np.zeros((width,), np.int32)
        return self.executor.compile("decode", self._fn, (table, host))

    @program_lookup
    def _restore_program(self, n):
        ids = self._jnp.zeros((n,))  # lint: hotloop-ok the fixture's designated array
        return self.executor.compile("restore", self._fn, (ids,))

    def _init_device_state(self):
        # not a lookup: the engine's own state is made here
        self._tokens = jnp.zeros((self.n_slots,), dtype=jnp.int32)
