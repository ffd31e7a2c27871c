"""One buffer each way across the host–device boundary (PR 36).

A merged policy call's batch inputs cross to the device as ONE flat
buffer and its outputs come back as ONE: a transfer costs the host a
fixed 0.13–0.25 ms whatever its bytes (PERF.md section 6, PR 36), and
an entry parameter `u8[32,72,96,3]` is laid out channels apart on a
TPU, so the host reorders its NHWC bytes on every call before the copy
can start.

The buffer is a vector of 32-bit WORDS, the TPU's native width: every
array is a REGION of it, beginning on a multiple of `ALIGN` bytes, its
bytes as numpy lays them out (C order, little-endian). On the host a
region is a numpy VIEW of the buffer (`host_views`); in a jitted
program it is sliced out at a static offset and bitcast to its dtype
(`unpack`), and a program's outputs are bitcast to words and
concatenated (`pack`). A 4-byte dtype is a same-width bitcast, which
costs nothing; narrower ones go through `u8[n, 4]` / `u16[n, 2]`; there
are no wider ones (the programs run without x64, and the server hands
an observation over in the dtype jit would give it). (A
`u8[N]` parameter would cost every 4-byte region a width-changing
bitcast, which XLA hoists over the WHOLE buffer: 0.29 ms a call at
`fleet32`'s sizes, measured.)

Interleaved bytes (`planar=True`, the server's choice where the program
runs on a TPU). A channels-last image `u8[rows, H, W, C]` is what a
convolution reads with C apart (the layout XLA itself gives such an
entry parameter: `{2,1,3,0}`); reading it C-minor costs the first
convolution twelve times its time. De-interleaving bytes is what a
vector unit is worst at, and what a matrix unit does for nothing: each
of a word's four bytes (a shift and a mask: elementwise) goes through
a 0/1 matrix that sends byte k of word q to its (channel, column), in
bfloat16 with float32 accumulation, which is exact for 0–255. The
values are the same, bit for bit; only the order in memory differs.
"""

import functools
from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

ALIGN = 128         # bytes; a multiple of the word and of a lane row
WORD = np.dtype(np.uint32)
_NARROW = {1: jnp.uint8, 2: jnp.uint16}


class Layout(NamedTuple):
  """Which arrays a buffer holds, in order: hashable, so a jitted step
  takes it as a static argument."""
  specs: Tuple[Tuple[str, Tuple[int, ...]], ...]  # (dtype name, shape)

  @classmethod
  def of_rows(cls, meta, rows):
    """`meta`: [(dtype, trailing shape)] per array, each `rows` tall."""
    return cls(tuple((np.dtype(dtype).name, (rows,) + tuple(trail))
                     for dtype, trail in meta))

  @classmethod
  def of_arrays(cls, arrays):
    return cls(tuple((np.dtype(a.dtype).name, tuple(a.shape))
                     for a in arrays))

  @property
  def regions(self):
    """([(offset, bytes)] per array, the buffer's bytes)."""
    return _regions(self.specs)

  @property
  def words(self):
    return self.regions[1] // WORD.itemsize

  @property
  def logical_bytes(self):
    return sum(nbytes for _, nbytes in self.regions[0])


@functools.lru_cache(maxsize=None)
def _regions(specs):
  found, offset = [], 0
  for name, shape in specs:
    nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(name).itemsize
    found.append((offset, nbytes))
    offset = -(-(offset + nbytes) // ALIGN) * ALIGN
  return tuple(found), offset


def host_views(words, layout):
  """The arrays of `layout` as numpy VIEWS of `words` (u32 [layout.words],
  C-contiguous): writing one writes the buffer."""
  raw = words.view(np.uint8)
  return [raw[offset:offset + nbytes].view(name).reshape(shape)
          for (name, shape), (offset, nbytes)
          in zip(layout.specs, layout.regions[0])]


def _interleaved(name, shape):
  """A channels-last image of bytes whose rows are whole words."""
  return (name == 'uint8' and len(shape) >= 3 and 1 < shape[-1] <= 4
          and shape[-2] * shape[-1] % WORD.itemsize == 0)


@functools.lru_cache(maxsize=None)
def _byte_routes(width, channels):
  """[4, words a row, channels * width] of 0/1: byte k of word q of an
  image row is (column w, channel c) where 4 q + k = channels * w + c,
  and goes to c * width + w."""
  routes = np.zeros((WORD.itemsize, width * channels // WORD.itemsize,
                     channels * width), np.float32)
  index = np.arange(width * channels)
  w, c = index // channels, index % channels
  routes[index % WORD.itemsize, index // WORD.itemsize, c * width + w] = 1
  return routes


def _planes(region, shape):
  """`region` (the words of a `u8[..., W, C]` image, C-minor) as that
  array, computed channel by channel: module docstring."""
  *lead, width, channels = shape
  rows_of_words = region.reshape(-1, width * channels // WORD.itemsize)
  routes = _byte_routes(width, channels)
  planar = None
  for k in range(WORD.itemsize):
    byte = ((rows_of_words >> (8 * k)) & 0xFF).astype(jnp.bfloat16)
    routed = jnp.dot(byte, jnp.asarray(routes[k], jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    planar = routed if planar is None else planar + routed
  planar = planar.astype(jnp.uint8).reshape(*lead, channels, width)
  return jnp.swapaxes(planar, -1, -2)


def unpack(words, layout, planar=False):
  """The arrays of `layout` out of `words` (u32 [layout.words]), inside
  a jitted program: static slices and bitcasts."""
  arrays = []
  with jax.named_scope('unpack'):
    for (name, shape), (offset, nbytes) in zip(layout.specs,
                                               layout.regions[0]):
      dtype = np.dtype(name)
      count = int(np.prod(shape, dtype=np.int64))
      region = jax.lax.slice(
          words, (offset // WORD.itemsize,),
          (-(-(offset + nbytes) // WORD.itemsize),))
      if planar and _interleaved(name, shape):
        arrays.append(_planes(region, shape))
      elif dtype.itemsize == WORD.itemsize:
        arrays.append(jax.lax.bitcast_convert_type(
            region, dtype).reshape(shape))
      else:
        narrow = jax.lax.bitcast_convert_type(
            region, _NARROW[dtype.itemsize]).reshape(-1)[:count]
        narrow = narrow.reshape(shape)
        if dtype == np.bool_:
          arrays.append(narrow != 0)
        else:
          arrays.append(jax.lax.bitcast_convert_type(narrow, dtype))
  return arrays


def pack(arrays):
  """`arrays` as ONE u32 vector and its Layout, inside a jitted
  program: what `host_views` reads on the other side."""
  layout = Layout.of_arrays(arrays)
  parts, at = [], 0
  with jax.named_scope('pack'):
    for array, (offset, nbytes) in zip(arrays, layout.regions[0]):
      if offset > at:
        parts.append(jnp.zeros(((offset - at) // WORD.itemsize,), WORD))
      itemsize = np.dtype(array.dtype).itemsize
      if array.dtype == jnp.bool_:
        array = array.astype(jnp.uint8)
      if itemsize == WORD.itemsize:
        region = jax.lax.bitcast_convert_type(array, WORD).reshape(-1)
      else:
        per_word = WORD.itemsize // itemsize
        narrow = jax.lax.bitcast_convert_type(
            array, _NARROW[itemsize]).reshape(-1)
        narrow = jnp.pad(narrow, (0, -narrow.shape[0] % per_word))
        region = jax.lax.bitcast_convert_type(
            narrow.reshape(-1, per_word), WORD)
      parts.append(region)
      at = offset + region.shape[0] * WORD.itemsize
    if layout.regions[1] > at:
      parts.append(jnp.zeros(((layout.regions[1] - at) // WORD.itemsize,),
                             WORD))
    return jnp.concatenate(parts), layout
