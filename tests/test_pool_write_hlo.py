"""The pool writes, compiled for the v5e without one.

Every write into a K/V pool (``inference/paged_cache.py``) is
page-granular and runs on a donated pool, so that the program holds no
operation of the pool's size but the in-place scatter on the aliased
parameter. A ROW scatter into the pool compiles, on the TPU, to two
whole-pool layout copies a layer (805 MB each at the serving cells'
shapes: two thirds of the device's busy time before PR 29), donated or
not. These tests compile each write at the three serving cells' pool
shapes for the described chip and read the HLO: no ``copy`` or
``transpose`` whose result has the pool's shape, and the pool aliased
to the output. They keep the copy from coming back with the next change
to the pool's shape or to a write.

All in this one file, the topology described inside a fixture: only
the worker that runs the file loads the TPU's library."""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.inference import paged_cache as pc

BS, HD = 16, 128
# (blocks, kv heads, table width, a mixed step's prompt chunk): the
# pools of gpt3-6.7b.chat / .doc and of trinity-large.mixed-queue
CELLS = {"gpt3-6.7b": (3072, 32, 128, 256),
         "trinity-large": (10240, 8, 784, 2048)}
SLOTS = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _programs(cell):
    """name -> (fn, statics, operand shapes after pool and scales)."""
    nb, h, mb, chunk = CELLS[cell]
    f32, i32 = jnp.float32, jnp.int32

    def kv(b, n):
        return [((b, n, h, HD), f32)] * 2

    def ragged(rows, q_lens):
        pages = 1 + sum(pc._pages_spanned(q, BS) for q in q_lens)
        return (pc._ragged_append, (),
                kv(1, rows) + [((pages,), i32), ((2, rows), i32)])

    return {
        "decode": (pc._append_rows, (BS, 1),
                   kv(SLOTS, 1) + [((SLOTS,), i32), ((SLOTS, mb), i32)]),
        "verify": (pc._append_rows, (BS, 4),
                   kv(SLOTS, 4) + [((SLOTS,), i32), ((SLOTS, mb), i32)]),
        "chunk": (pc._append_rows, (BS, chunk),
                  kv(1, chunk) + [((1,), i32), ((1, mb), i32),
                                  ((1,), i32)]),
        "ragged_decode": ragged(SLOTS, (1,) * SLOTS),
        "ragged_mixed": ragged(chunk + SLOTS, (chunk,) + (1,) * SLOTS),
        "block_copy": (pc._block_copy, (), [((1,), i32)] * 2),
        "prefill_scatter": (pc._prefill_scatter, (0, 8, BS),
                            [((2, 1, h, 8 * BS, HD), f32), ((8,), i32)]),
    }


def _pool_sized_moves(hlo: str, shape) -> list:
    dims = ",".join(str(d) for d in shape)
    made = re.compile(r"= \w+\[" + dims + r"\]\S* (copy|transpose)\(")
    return [line.strip()[:160] for line in hlo.splitlines()
            if made.search(line)]


def _compile(program, shapes, one_chip) -> str:
    args = [None if s is None
            else jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return program.lower(*args).compile().as_text()


@pytest.mark.parametrize("write", ["decode", "verify", "chunk",
                                   "ragged_decode", "ragged_mixed",
                                   "block_copy", "prefill_scatter"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_write_moves_no_whole_pool(one_chip, cell, write):
    nb, h = CELLS[cell][:2]
    pool = (nb, 2, h, BS, HD)
    fn, static, rest = _programs(cell)[write]
    # _pool_program's jit: the program PagedKVCache._write_pool runs
    hlo = _compile(pc._pool_program(fn, *static),
                   [(pool, jnp.bfloat16), None] + rest, one_chip)
    assert not _pool_sized_moves(hlo, pool), _pool_sized_moves(hlo, pool)
    head = hlo.splitlines()[0]
    assert "input_output_alias" in head and "(0, {}" in head, head[:300]


# the latent pool of joyai-flash.long-decode: ONE row a position, no V
# plane (blocks, table width, prompt chunk, slots): 576 columns stored
# as 640, whole 128-lane tiles
LATENT = (25600, 896, 2048, 64)
LATENT_WIDTH = 640


def _latent_programs():
    nb, mb, chunk, slots = LATENT
    f32, i32 = jnp.float32, jnp.int32

    def row(b, n):                     # the row, and no value
        return [((b, n, 1, LATENT_WIDTH), f32), None]

    def ragged(rows, q_lens):
        pages = 1 + sum(pc._pages_spanned(q, BS) for q in q_lens)
        return (pc._ragged_append, (),
                row(1, rows) + [((pages,), i32), ((2, rows), i32)])

    return {
        "decode": (pc._append_rows, (BS, 1),
                   row(slots, 1) + [((slots,), i32), ((slots, mb), i32)]),
        "chunk": (pc._append_rows, (BS, chunk),
                  row(1, chunk) + [((1,), i32), ((1, mb), i32),
                                   ((1,), i32)]),
        "ragged_decode": ragged(slots, (1,) * slots),
        "ragged_mixed": ragged(chunk + slots, (chunk,) + (1,) * slots),
        "ragged_two_chunks": ragged(chunk + slots, (chunk // 2,) * 2
                                    + (1,) * slots),
        "block_copy": (pc._block_copy, (), [((1,), i32)] * 2),
    }


@pytest.mark.parametrize("write", ["decode", "chunk", "ragged_decode",
                                   "ragged_mixed", "ragged_two_chunks",
                                   "block_copy"])
def test_latent_write_moves_no_whole_pool(one_chip, write):
    """The latent page form (one plane, one head of 576): the same
    page-granular write on the donated pool, no move of the pool's
    size."""
    pool = (LATENT[0], 1, 1, BS, LATENT_WIDTH)
    fn, static, rest = _latent_programs()[write]
    hlo = _compile(pc._pool_program(fn, *static),
                   [(pool, jnp.bfloat16), None] + rest, one_chip)
    assert not _pool_sized_moves(hlo, pool), _pool_sized_moves(hlo, pool)
    head = hlo.splitlines()[0]
    assert "input_output_alias" in head and "(0, {}" in head, head[:300]


def test_a_latent_row_of_576_columns_would_copy_the_pool(one_chip):
    """Why the row is stored 640 wide: at 576 (4.5 lane tiles) the
    compiler lays the pool out with the block axis minor and the same
    page write copies it there and back."""
    pool = (LATENT[0], 1, 1, BS, 576)
    fn, static, rest = _latent_programs()["ragged_decode"]
    rest = [((1, LATENT[3], 1, 576), jnp.float32)] + rest[1:]
    hlo = _compile(pc._pool_program(fn, *static),
                   [(pool, jnp.bfloat16), None] + rest, one_chip)
    assert len(_pool_sized_moves(hlo, pool)) == 2


def test_int8_pool_and_scales_alias(one_chip):
    """The int8 twin: payload and scale pages written page-granular,
    both aliased; no move of the int8 pool's size."""
    nb, h, mb, chunk = CELLS["gpt3-6.7b"]
    pool = (nb, 2, h, BS, HD)
    fn, static, rest = _programs("gpt3-6.7b")["ragged_mixed"]
    hlo = _compile(pc._pool_program(fn, *static),
                   [(pool, jnp.int8), (pool[:4], jnp.float32)] + rest,
                   one_chip)
    assert not _pool_sized_moves(hlo, pool)
    head = hlo.splitlines()[0]
    assert "{0}: (0, {}" in head and "{1}: (1, {}" in head, head[:300]


def test_the_reading_finds_a_row_scatter(one_chip):
    """The control: the row scatter these writes replaced still
    compiles to two pool-sized layout copies, donated, so the reading
    above is not blind."""
    nb, h = CELLS["gpt3-6.7b"][:2]
    pool = (nb, 2, h, BS, HD)

    def row_scatter(pool, k, v, blk, off):
        pool = pool.at[blk, 0, :, off, :].set(k[0].astype(pool.dtype))
        return pool.at[blk, 1, :, off, :].set(v[0].astype(pool.dtype))

    hlo = _compile(jax.jit(row_scatter, donate_argnums=(0,)),
                   [(pool, jnp.bfloat16)]
                   + [((1, SLOTS, h, HD), jnp.float32)] * 2
                   + [((SLOTS,), jnp.int32)] * 2, one_chip)
    assert len(_pool_sized_moves(hlo, pool)) == 2


# the K/V pools of lfm2-24b.busy-chat: heads of 64, stored TWO a 128-lane
# row (blocks, stored rows a position, table width, prompt chunk, slots)
PACKED = (24000, 4, 304, 1024, 192)


def _packed_programs(width=HD, rows=PACKED[1]):
    nb, _, mb, chunk, slots = PACKED
    bf16, i32 = jnp.bfloat16, jnp.int32

    def kv(b, n):
        return [((b, n, rows, width), bf16)] * 2

    def ragged(n_rows, q_lens):
        pages = 1 + sum(pc._pages_spanned(q, BS) for q in q_lens)
        return (pc._ragged_append, (),
                kv(1, n_rows) + [((pages,), i32), ((2, n_rows), i32)])

    return {
        "decode": (pc._append_rows, (BS, 1),
                   kv(slots, 1) + [((slots,), i32), ((slots, mb), i32)]),
        "chunk": (pc._append_rows, (BS, chunk),
                  kv(1, chunk) + [((1,), i32), ((1, mb), i32),
                                  ((1,), i32)]),
        "ragged_decode": ragged(slots, (1,) * slots),
        "ragged_mixed": ragged(chunk + slots, (chunk,) + (1,) * slots),
        "ragged_two_chunks": ragged(chunk + slots, (chunk // 2,) * 2
                                    + (1,) * slots),
        "block_copy": (pc._block_copy, (), [((1,), i32)] * 2),
    }


@pytest.mark.parametrize("write", ["decode", "chunk", "ragged_decode",
                                   "ragged_mixed", "ragged_two_chunks",
                                   "block_copy"])
def test_two_heads_a_row_write_moves_no_whole_pool(one_chip, write):
    """Heads of 64 stored two a 128-lane row ([24 000, 2, 4, 16, 128]):
    the same page-granular write on the donated pool, no move of the
    pool's size."""
    pool = (PACKED[0], 2, PACKED[1], BS, HD)
    fn, static, rest = _packed_programs()[write]
    hlo = _compile(pc._pool_program(fn, *static),
                   [(pool, jnp.bfloat16), None] + rest, one_chip)
    assert not _pool_sized_moves(hlo, pool), _pool_sized_moves(hlo, pool)
    head = hlo.splitlines()[0]
    assert "input_output_alias" in head and "(0, {}" in head, head[:300]


@pytest.mark.parametrize("write", ["decode", "ragged_decode",
                                   "ragged_mixed"])
def test_a_pool_row_of_64_columns_would_copy_the_pool(one_chip, write):
    """Why the heads are paired: at one head of 64 a row (half a lane
    tile; [24 000, 2, 8, 16, 64]) the compiler lays the pool out with the
    block axis minor and the same page write copies it there and back."""
    pool = (PACKED[0], 2, 8, BS, 64)
    fn, static, rest = _packed_programs(width=64, rows=8)[write]
    hlo = _compile(pc._pool_program(fn, *static),
                   [(pool, jnp.bfloat16), None] + rest, one_chip)
    assert len(_pool_sized_moves(hlo, pool)) == 2
