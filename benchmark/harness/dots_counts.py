"""What one merged policy call of the sequence policy with latent
attention (MLA) and routed experts must compute and move, from the
configuration's shapes and from what the server counted of the calls:
the counts the whole call's and two scopes' roofline shares divide by
the device's time.

Counted by what is LIVE in the call (a padded row's work is the
implementation's, not the algorithm's), at the stated dtypes:
bfloat16 parameters and cache, float32 router bias and value head.
These are the LEAST any implementation has to do, so a share over 100%
would mean a fault in the time it is divided by:

- parameters: attention, router, shared expert, dense MLP, the norms
  and the two heads are read once a call whatever the rows; of the
  routed experts only those some live row chose (`experts_hit`, which
  the routed layers count a call and the server sums); of the embedding
  only the rows' own lines;
- the latent cache: per live row and layer the `kv_lora_rank +
  qk_rope_head_dim` numbers of every token of the row's episode so far,
  the one this call writes among them (`cache_tokens_read`, the sum of
  the live rows' positions and one, which the server follows on the
  host);
- FLOPs: a multiply-add is 2; the matrices by the rows; a routed
  expert by the rows that chose it (`routed_rows_held`); attention in
  its absorbed form, per cached token, layer and head the score over
  `rank + rope` and the weighted sum over `rank` numbers.

`per_call` is what a merged call carried in the mean: the deltas of
the server's counters over the calls they were counted in
(`readers/trace_counted_share.py` makes it), by the counters' names.

Nothing here knows a cell: the shapes come from the program's Config
(`seq_*`, `num_actions`), as `brumby_counts.py` takes its own.
"""


def shapes(config):
  return dict(
      layers=config.seq_num_layers, dense=config.seq_first_dense_layers,
      hidden=config.seq_hidden_size, heads=config.seq_num_heads,
      q_rank=config.seq_q_lora_rank, rank=config.seq_kv_lora_rank,
      nope=config.seq_qk_nope_head_dim, rope=config.seq_qk_rope_head_dim,
      v=config.seq_v_head_dim, mlp=config.seq_mlp_size,
      moe=config.seq_moe_size, routed=config.seq_routed_experts,
      held=config.seq_experts_held, shared=config.seq_shared_experts,
      vocab=config.num_actions, capacity=config.seq_cache_capacity)


def attention_matrices(s):
  """q_a, q_b, kv_a, kv_b and the output projection."""
  return (s['hidden'] * s['q_rank'] +
          s['q_rank'] * s['heads'] * (s['nope'] + s['rope']) +
          s['hidden'] * (s['rank'] + s['rope']) +
          s['rank'] * s['heads'] * (s['nope'] + s['v']) +
          s['heads'] * s['v'] * s['hidden'])


def attention_parameters(s):
  """The matrices and the two latent norms."""
  return attention_matrices(s) + s['q_rank'] + s['rank']


def ffn_parameters(s, width):
  return 3 * s['hidden'] * width


def layer_parameters(s, routed):
  """A block: attention, its two norms over the hidden size, and the
  dense MLP, or the router (with its bias), the shared expert and the
  routed experts held."""
  common = attention_parameters(s) + 2 * s['hidden']
  if not routed:
    return common + ffn_parameters(s, s['mlp'])
  return (common + s['hidden'] * s['routed'] + s['routed'] +
          ffn_parameters(s, s['moe'] * s['shared']) +
          s['held'] * ffn_parameters(s, s['moe']))


def parameters(config):
  """Embedding, blocks, final norm, untied policy head, value head."""
  s = shapes(config)
  return (s['dense'] * layer_parameters(s, False) +
          (s['layers'] - s['dense']) * layer_parameters(s, True) +
          2 * s['vocab'] * s['hidden'] + s['hidden'] + s['hidden'] + 1)


def cache_bytes_per_token(config):
  """One token of one session, all layers."""
  s = shapes(config)
  return 2 * s['layers'] * (s['rank'] + s['rope'])


def state_bytes_per_slot(config):
  """A slot's cache at its capacity, and its int32 position."""
  return shapes(config)['capacity'] * cache_bytes_per_token(config) + 4


def cache_bytes(config, per_call):
  """The cached tokens the live rows read, the one each writes among
  them."""
  return per_call['cache_tokens_read'] * cache_bytes_per_token(config)


def attend_flops(config, per_call):
  s = shapes(config)
  return (per_call['cache_tokens_read'] * s['layers'] * 2 * s['heads'] *
          (2 * s['rank'] + s['rope']))


def experts_bytes(config, per_call):
  """The weights of the routed experts that some live row chose."""
  s = shapes(config)
  return per_call['experts_hit'] * 2 * ffn_parameters(s, s['moe'])


def _matrices(s):
  """The weights every live row is multiplied by, whatever the call
  carries: all but the routed experts (the value head's among them)."""
  routed = s['layers'] - s['dense']
  return (s['layers'] * attention_matrices(s) +
          s['dense'] * ffn_parameters(s, s['mlp']) +
          routed * (s['hidden'] * s['routed'] +
                    ffn_parameters(s, s['moe'] * s['shared'])) +
          s['vocab'] * s['hidden'] + s['hidden'])


def call_bytes(config, per_call):
  s = shapes(config)
  # Read whole every call: the matrices and the norms in bfloat16; the
  # routers' biases and the value head (weights and bias) in float32.
  norms = s['layers'] * (s['q_rank'] + s['rank'] + 2 * s['hidden']) + (
      s['hidden'])
  bf16 = _matrices(s) - s['hidden'] + norms
  f32 = (s['layers'] - s['dense']) * s['routed'] + s['hidden'] + 1
  return (2 * (bf16 + per_call['requests'] * s['hidden']) + 4 * f32 +
          experts_bytes(config, per_call) + cache_bytes(config, per_call))


def call_flops(config, per_call):
  s = shapes(config)
  return (2 * per_call['requests'] * _matrices(s) +
          2 * per_call['routed_rows_held'] * ffn_parameters(s, s['moe']) +
          attend_flops(config, per_call))
