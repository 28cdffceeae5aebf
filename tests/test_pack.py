"""models/pack: a dict of host arrays through one word buffer and back,
bit for bit, and an unpack program whose build time does not grow with
the buffer (a 500-template wave ships 17 MB of pod rows)."""

import time

import numpy as np
import pytest

import kubernetes_tpu  # noqa: F401  (x64 before jax)
from kubernetes_tpu.models.pack import WORD, Packer, pack_arrays, unpack

DTYPES = [np.bool_, np.int8, np.uint8, np.int16, np.uint16, np.float16,
          np.int32, np.uint32, np.float32, np.int64, np.uint64, np.float64]


def _random(rng, dtype, shape):
    """Every bit pattern of the dtype, NaNs and negative zeros among
    them (bool: the two values numpy holds)."""
    dt = np.dtype(dtype)
    n = int(np.prod(shape, dtype=np.int64))
    if dt == np.bool_:
        return rng.integers(0, 2, size=shape).astype(bool)
    raw = rng.integers(0, 256, size=n * dt.itemsize, dtype=np.uint8)
    return raw.view(dt).reshape(shape)


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_every_dtype_round_trips_bit_for_bit(dtype):
    """Beside neighbours of other widths, at sizes that end inside a
    word, as a 0-d array and with a zero-size axis."""
    rng = np.random.default_rng(np.dtype(dtype).num)
    arrays = {
        "a_odd": _random(rng, dtype, (13,)),
        "b_matrix": _random(rng, dtype, (5, 3, 7)),
        "c_scalar": _random(rng, dtype, ()),
        "d_empty": np.zeros((0, 4), dtype),
        "e_empty_inner": np.zeros((3, 0), dtype),
        "f_one": _random(rng, dtype, (1,)),
        "neighbour_i64": _random(rng, np.int64, (3,)),
        "neighbour_u8": _random(rng, np.uint8, (5,)),
    }
    layout, buf = pack_arrays(arrays)
    assert buf.dtype == WORD and buf.nbytes % 8 == 0
    shipped = Packer().ship(arrays)
    assert set(shipped) == set(arrays)
    for name, want in arrays.items():
        _same_bits(shipped[name], want)
    # the traceable form, as the wave programs call it inside a jit
    import jax

    inside = jax.jit(lambda b: unpack(layout, b))(buf)
    for name, want in arrays.items():
        _same_bits(inside[name], want)


def test_empty_dict_and_only_empty_fields_ship():
    assert Packer().ship({}) == {}
    out = Packer().ship({"none": np.zeros((0,), np.int64)})
    _same_bits(out["none"], np.zeros((0,), np.int64))


def test_item_sizes_the_words_cannot_hold_are_refused():
    with pytest.raises(TypeError):
        pack_arrays({"c": np.zeros(3, np.complex128)})


def test_bytes_shipped_are_counted():
    packer = Packer()
    before = Packer.total_h2d_bytes
    packer.ship({"a": np.zeros(10, np.int64), "b": np.zeros(3, np.bool_)})
    assert packer.h2d_bytes == 80 + 8
    assert Packer.total_h2d_bytes - before == 88


#: seconds a 17 MB layout's unpack program may take to trace, lower and
#: compile on a CPU (it takes under one; the uint8 form this replaced
#: took minutes for the chip at this size)
BIG_BUILD_S = 20.0


def test_a_500_template_waves_pod_rows_build_their_program_quickly():
    """The pod rows of a 4,096-pod wave over 500 controllers:
    spread_match i64[4096, 500] beside smaller fields of every width."""
    rng = np.random.default_rng(7)
    arrays = {"spread_match": _random(rng, np.int64, (4096, 500)),
              "label_kv": _random(rng, np.uint32, (4096, 94)),
              "class_id": _random(rng, np.int32, (4096,)),
              "zero_req": _random(rng, np.bool_, (4096,)),
              "port_mask": _random(rng, np.uint32, (4096, 2)),
              "pref_num": _random(rng, np.float64, (4096, 1, 1))}
    assert sum(a.nbytes for a in arrays.values()) >= 16 << 20
    packer = Packer()
    began = time.monotonic()
    out = packer.ship(arrays)
    for a in out.values():
        a.block_until_ready()
    took = time.monotonic() - began
    assert took < BIG_BUILD_S, f"{took:.1f}s to build and run the unpack"
    for name, want in arrays.items():
        _same_bits(out[name], want)
    assert len(packer._unpack) == 1
    packer.ship(arrays)  # the same layout: the program is reused
    assert len(packer._unpack) == 1
