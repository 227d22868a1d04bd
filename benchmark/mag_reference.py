"""What the MAG240M configuration names as its `reference`: the plain
reference of `reference/gat2bn.py` (`loss`, `init_extra`, `param_shapes`,
untouched and importing nothing of the program), behind ONE check made
before anything is placed: that the program's model class takes the
configuration's `model.kwargs`.

Why here: `param_shapes` is the first thing of a configuration the
harness resolves by name (`check.make_weights`, right after the tables
are made and before `cell.Program` quantises and places them), and this
configuration's float32 feature table is 11.7 GB on the host. A program
that lacks one of its fields (`norm`, `head_dim`: any commit before PR
32) would raise the same `TypeError` from `cell.Program`, but only after
`DeviceFeatureStore.from_arrays`, whose one-pass quantisation before PR
32 held four table-sized float32 transients: on the one-chip machine's
40 GiB that process is killed (my chip run, PR 32: exit 137 at 39.4 GB,
50 s after the tables) and says nothing. Raised here it is an error in
half a minute.

**And one rebinding, said loudly because it reaches into accepted
benchmark code without editing its file.** `reference/common.
quantize_int8` (the reference's own quantisation, called by
`check.place_tables` with its default `chunk_rows` = 262,144) sizes its
chunks in ROWS: at the accepted cells' 128 columns a chunk's float32 is
134 MB, at this table's 768 it is 805 MB, and every chunk makes five
such arrays on one of eight threads. Arrays of that size are mapped and
unmapped one by one (they are over glibc's 32 MiB mmap ceiling), and on
the one-chip machine that churn costs free memory faster than the
kernel gives it back: my chip runs, PR 32, read `MemFree` falling by
about 0.4 bytes for every byte so allocated, in no field of
`/proc/meminfo` and not in the process's resident set, and coming back
only seconds later. Over this table's 81 GB of churn the machine's 40
GiB were met inside the reference's table placement, three runs in
three, the program's own part long done and freed, at 805 MB and at 134
MB a chunk alike; chunks of 8-16 MB, which the allocator recycles
without a system call, lose nothing (the same runs; the program's own
quantisation works in such chunks). `param_shapes` below therefore
sets that default to the rows that make 16 MB of float32 a chunk at
this configuration's width (5,461), for this process. The function's
arithmetic is per element and per column, so the table and the scale
are the same bytes at any chunking
(`tests/benchmark_checks/test_mag_cell.py` compares them); nothing else
of the harness is touched, and no other configuration imports this
module. What the file itself needs, for the next `benchmark` PR: chunk
by bytes (`chunk_rows = max(1, (1 << 22) // feat.shape[1])`), then
delete this rebinding (PERF.md section 7; ROADMAP C2).
"""

from __future__ import annotations

from .cell import resolve
from .reference import common, gat2bn

loss = gat2bn.loss
init_extra = gat2bn.init_extra

# rows of a reference quantisation chunk: 16 MB of float32 at this
# configuration's 768 columns
_ACCEPTED_CHUNK_ROWS = 262_144
CHUNK_ROWS = (1 << 22) // 768


def param_shapes(cfg: dict) -> dict:
    """reference/gat2bn.param_shapes, after building the configuration's
    model as `cell.Program` will (its class with its kwargs: a dataclass
    constructor; nothing is traced or placed) and after the rebinding."""
    kwargs = {k: tuple(v) if isinstance(v, list) else v
              for k, v in cfg["model"]["kwargs"].items()}
    resolve(cfg["model"]["class"])(**kwargs)
    if common.quantize_int8.__defaults__ == (_ACCEPTED_CHUNK_ROWS,):
        # the rebinding the module's text speaks of; a file that no
        # longer chunks by 262,144 rows needs none of it
        common.quantize_int8.__defaults__ = (CHUNK_ROWS,)
    return gat2bn.param_shapes(cfg)
