"""ArrayTable: 1-D dense sharded parameter vector.

TPU-native equivalent of the reference ArrayTable
(ref: include/multiverso/table/array_table.h, src/table/array_table.cpp).
The reference shards contiguous ranges across server processes
(src/table/array_table.cpp:11-21) and hand-partitions each Add/Get blob per
server (:68-95). Here the contiguous-range sharding is exactly a
``NamedSharding(mesh, P(axis))`` over the table mesh axis — XLA emits the
shard-wise scatter/gather the reference hand-rolled, and the updater runs on
all shards in parallel (:116-141 -> updaters/__init__.py).
"""

from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp

from multiverso_tpu import updaters as updaters_lib
from multiverso_tpu.table import Table


class ArrayTable(Table):
    def __init__(self, size: int, dtype=jnp.float32,
                 updater: Union[str, updaters_lib.Updater, None] = None,
                 name: str = "array",
                 init=None, seed: Optional[int] = None,
                 init_scale: float = 0.0):
        super().__init__((int(size),), dtype=dtype, updater=updater,
                         name=name, init=init, seed=seed,
                         init_scale=init_scale)

    @property
    def size(self) -> int:
        return self.shape[0]


class ArrayTableOption:
    """ref DEFINE_TABLE_TYPE option struct (table_interface.h:77-80) parity:
    ``mv.create_table(ArrayTableOption(size))``."""

    def __init__(self, size: int, dtype=jnp.float32, updater=None,
                 init=None, seed=None, init_scale: float = 0.0):
        self.size = size
        self.dtype = dtype
        self.updater = updater
        self.init = init
        self.seed = seed
        self.init_scale = init_scale

    def build(self, name: str = "array") -> ArrayTable:
        return ArrayTable(self.size, dtype=self.dtype, updater=self.updater,
                          name=name, init=self.init, seed=self.seed,
                          init_scale=self.init_scale)
