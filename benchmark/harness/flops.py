"""FLOPs the agent's forward and backward passes need, from shapes.

Counted: convolutions, dense layers, LSTM gates, heads (one multiply-
add = 2 FLOPs). Not counted: elementwise work, pooling, V-trace, the
optimizer, anything recomputed. Backward = 2 x forward. The numbers
come from the configuration's flags and the layer definitions in
scalable_agent_tpu/models/{torsos,agent,instruction}.py as they stood
when this file was written. An unknown torso or an uncounted head is an
error, not a zero: a configuration with other layers brings its count
in a file of its own, beside the reader that names it.
"""

import math

# models/instruction.py
_INSTR_LEN = 16
_INSTR_EMBED = 20
_INSTR_LSTM = 64
# models/agent.py, models/torsos.py
_HIDDEN = 256
_TORSO_OUT = 256
_DEEP_SECTIONS = ((16, 2), (32, 2), (32, 2))


def conv2d_flops(h_out, w_out, kh, kw, c_in, c_out):
  return 2 * h_out * w_out * kh * kw * c_in * c_out


def dense_flops(n_in, n_out):
  return 2 * n_in * n_out


def lstm_step_flops(n_in, hidden):
  """One step of one sequence: four gates over [input, hidden]."""
  return 2 * (n_in + hidden) * 4 * hidden


def _same_stride2(n):
  return math.ceil(n / 2)


def torso_forward_flops(torso, height, width, channels=3):
  """One frame through the visual torso."""
  if torso == 'deep':
    h, w, c_in, total = height, width, channels, 0
    for c_out, blocks in _DEEP_SECTIONS:
      # conv at full size, then 3x3/2 SAME max-pool
      total += conv2d_flops(h, w, 3, 3, c_in, c_out)
      h, w = _same_stride2(h), _same_stride2(w)
      total += blocks * 2 * conv2d_flops(h, w, 3, 3, c_out, c_out)
      c_in = c_out
    return total + dense_flops(h * w * c_in, _TORSO_OUT)
  raise ValueError(f'flops.py does not know the torso {torso!r}')


def agent_forward_flops(config):
  """One frame through the whole agent: torso, instruction encoder,
  one LSTM core step, policy and baseline heads."""
  if config.pixel_control_cost > 0 or config.use_popart:
    raise ValueError('flops.py does not count the pixel-control head '
                     'or PopArt yet; add them with the configuration '
                     'that turns them on')
  num_actions = config.num_actions
  if not num_actions:
    raise ValueError('the configuration must state num_actions')
  total = torso_forward_flops(config.torso, config.height, config.width)
  core_in = _TORSO_OUT + 1 + num_actions  # torso, reward, last action
  if config.resolved_use_instruction:
    total += _INSTR_LEN * lstm_step_flops(_INSTR_EMBED, _INSTR_LSTM)
    core_in += _INSTR_LSTM
  total += lstm_step_flops(core_in, _HIDDEN)
  total += dense_flops(_HIDDEN, num_actions) + dense_flops(_HIDDEN, 1)
  return total


def learner_step_flops(config):
  """One learner step: forward and backward over the [T+1, B] batch."""
  frames = (config.unroll_length + 1) * config.batch_size
  return 3 * agent_forward_flops(config) * frames


def anakin_step_flops(config):
  """One fused step: T acting passes of B (forward only), then the
  learner step over the [T+1, B] batch they made."""
  acting = (agent_forward_flops(config) * config.unroll_length *
            config.batch_size)
  return acting + learner_step_flops(config)
