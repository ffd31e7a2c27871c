"""runtime/packing.py (PR 36): arrays as regions of ONE buffer of
32-bit words: numpy views on the host, static slices and bitcasts in a
jitted program, the same bytes on both sides."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_agent_tpu.runtime import packing

ROWS = 6
META = [(np.int32, ()), (np.float32, ()), (np.bool_, ()),
        (np.uint8, (24, 32, 3)), (np.int32, (16,)), (np.float32, (256,)),
        (np.float16, (5,)), (np.int8, (3,)), (np.uint8, (7, 5, 3)),
        (np.uint8, (4, 8, 4)), (np.uint16, (3,)), (jnp.bfloat16, (2, 3))]


def _filled(layout, seed=0):
  """(the buffer, the values written through its views)."""
  rng = np.random.RandomState(seed)
  words = np.zeros((layout.words,), np.uint32)
  values = []
  for view in packing.host_views(words, layout):
    if view.dtype == np.bool_:
      value = rng.rand(*view.shape) < 0.5
    elif view.dtype.kind in 'fV' or view.dtype == jnp.bfloat16:
      value = rng.randn(*view.shape).astype(view.dtype)
    else:
      info = np.iinfo(view.dtype)
      value = rng.randint(max(info.min, -1000), min(info.max, 1000) + 1,
                          view.shape).astype(view.dtype)
    view[...] = value
    values.append(value)
  return words, values


def test_a_layout_is_aligned_hashable_and_nearly_all_payload():
  layout = packing.Layout.of_rows(META, ROWS)
  regions, total = layout.regions
  assert total == layout.words * 4 and total % packing.ALIGN == 0
  end = 0
  for (name, shape), (offset, nbytes) in zip(layout.specs, regions):
    assert offset % packing.ALIGN == 0 and offset >= end
    assert nbytes == int(np.prod(shape)) * np.dtype(name).itemsize
    end = offset + nbytes
  assert layout == packing.Layout.of_rows(META, ROWS)
  assert hash(layout) == hash(packing.Layout.of_rows(META, ROWS))
  assert layout != packing.Layout.of_rows(META, ROWS + 1)
  # fleet32's seven arrays: 96 B of alignment in 731,520.
  fleet = packing.Layout.of_rows(
      [(np.int32, ()), (np.float32, ()), (np.bool_, ()),
       (np.uint8, (72, 96, 3)), (np.int32, (16,)), (np.float32, (256,)),
       (np.float32, (256,))], 32)
  assert (fleet.logical_bytes, fleet.words * 4) == (731424, 731520)


def test_host_views_write_through_to_the_buffer():
  layout = packing.Layout.of_rows(META, ROWS)
  words, values = _filled(layout)
  raw = words.view(np.uint8)
  views = packing.host_views(words, layout)
  for view, value, (offset, nbytes) in zip(views, values,
                                           layout.regions[0]):
    assert np.shares_memory(view, words) and view.flags.c_contiguous
    assert raw[offset:offset + nbytes].tobytes() == value.tobytes()


@pytest.mark.parametrize('planar', [False, True],
                         ids=['bytes_as_they_lie', 'images_by_planes'])
def test_unpack_reads_what_the_host_wrote(planar):
  """Every dtype width, bit for bit; with `planar` the channels-last
  images go through the 0/1 products and come out the same."""
  layout = packing.Layout.of_rows(META, ROWS)
  words, values = _filled(layout, seed=1)
  arrays = jax.jit(
      lambda w: packing.unpack(w, layout, planar))(words)
  assert len(arrays) == len(values)
  for array, value in zip(arrays, values):
    assert array.dtype == value.dtype and array.shape == value.shape
    assert np.asarray(array).tobytes() == value.tobytes()


def test_which_arrays_count_as_interleaved_images():
  yes = [('uint8', (32, 72, 96, 3)), ('uint8', (4, 64, 64, 3)),
         ('uint8', (2, 8, 4)), ('uint8', (1, 5, 6, 2))]
  no = [('uint8', (32, 7, 5, 3)),   # a row of 15 B is no whole word
        ('uint8', (32, 16)),        # no channel axis
        ('int8', (32, 8, 8, 4)), ('bool', (32, 8, 8, 4)),
        ('uint8', (32, 8, 8, 1)), ('uint8', (32, 8, 8, 16)),
        ('float32', (32, 8, 8, 3))]
  assert all(packing._interleaved(*spec) for spec in yes)
  assert not any(packing._interleaved(*spec) for spec in no)
  routes = packing._byte_routes(96, 3)
  assert routes.shape == (4, 72, 288)
  # A permutation: every byte of a row goes to one place, once.
  assert (routes.sum((0, 1)) == 1).all() and routes.sum() == 288


@pytest.mark.parametrize('extra', [(), ((),), ((), (2,))],
                         ids=['rows', 'a_counter', 'two_counters'])
def test_pack_is_what_host_views_reads(extra):
  """A program's outputs, scalars among them, through `pack` and back
  on the host by the layout it returns; and `unpack(pack(x)) == x`."""
  layout = packing.Layout.of_rows(META, ROWS)
  words, values = _filled(layout, seed=2)
  counters = [np.full(shape, 7 + i, np.int32)
              for i, shape in enumerate(extra)]
  noted = {}

  def program(w):
    arrays = packing.unpack(w, layout) + [jnp.asarray(c) for c in counters]
    packed, noted['layout'] = packing.pack(arrays)
    again = packing.unpack(packed, noted['layout'])
    return packed, again

  packed, again = jax.jit(program)(words)
  packed = np.asarray(packed)
  out_layout = noted['layout']
  assert packed.dtype == np.uint32 and packed.shape == (out_layout.words,)
  read = packing.host_views(packed, out_layout)
  assert len(read) == len(values) + len(counters)
  for got, twice, want in zip(read, again, values + counters):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    assert np.asarray(twice).tobytes() == want.tobytes()
  # Between regions: zeros, not whatever was there.
  mask = np.ones(packed.nbytes, bool)
  for offset, nbytes in out_layout.regions[0]:
    mask[offset:offset + nbytes] = False
  assert not packed.view(np.uint8)[mask].any()
