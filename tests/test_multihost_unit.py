"""Unit half of the round-17 multi-process runtime: everything here
runs in ONE process (no jax.distributed spawn) — the spawning
acceptance harness is tests/test_multihost.py.

Covers the validate_distributed knob group (bad coordinator, count
mismatches, the anakin/SDC/TP cross-links), the staging arena's
process_index slot-placement arithmetic (unroll_slot_owners — pulled
out of make_unroll_assembly exactly so this file can test the
multi-process shapes without processes), the TP compute-mode
resolution, and the distributed.initialize seam's config plumbing.
"""

import dataclasses

import pytest

import jax

from scalable_agent_tpu.config import Config, validate_distributed
from scalable_agent_tpu.parallel import distributed
from scalable_agent_tpu.parallel import train_parallel


# --- validate_distributed: hard errors -------------------------------


def test_validate_distributed_accepts_single_host_default():
  assert validate_distributed(Config()) == []


def test_validate_distributed_bad_coordinator_forms():
  for bad in ('nocolon', ':123', 'host:', 'host:notaport'):
    with pytest.raises(ValueError, match='host:port'):
      validate_distributed(Config(coordinator_address=bad,
                                  num_processes=2))


def test_validate_distributed_count_mismatches():
  with pytest.raises(ValueError, match='num_processes'):
    validate_distributed(Config(num_processes=0))
  # Declared multi-process without a coordinator: nothing to join.
  with pytest.raises(ValueError, match='coordinator_address'):
    validate_distributed(Config(num_processes=2))
  # process_id out of the declared range (explicit and via task).
  with pytest.raises(ValueError, match='out of range'):
    validate_distributed(Config(coordinator_address='h:1',
                                num_processes=2, process_id=2))
  with pytest.raises(ValueError, match='out of range'):
    validate_distributed(Config(coordinator_address='h:1',
                                num_processes=2, task=5))
  # In-range ids pass.
  assert validate_distributed(
      Config(coordinator_address='h:1', num_processes=2,
             process_id=1)) == []


def test_validate_distributed_tp_compute_enum():
  with pytest.raises(ValueError, match='tp_compute'):
    validate_distributed(Config(tp_compute='bogus'))
  for ok in ('auto', 'sharded', 'gathered'):
    validate_distributed(Config(tp_compute=ok))


# --- validate_distributed: cross-links -------------------------------


def test_validate_distributed_anakin_is_a_hard_error():
  # Same verdict train_anakin reaches, but before any spin-up cost —
  # and it must fire from the LIVE topology too (the launcher path,
  # where the config fields stay default).
  with pytest.raises(ValueError, match='anakin'):
    validate_distributed(
        Config(coordinator_address='h:1', num_processes=2,
               runtime='anakin', env_backend='bandit'))
  with pytest.raises(ValueError, match='anakin'):
    validate_distributed(Config(runtime='anakin', env_backend='bandit'),
                         live_process_count=2)


def test_validate_distributed_sdc_allgather_cross_link():
  warnings = validate_distributed(
      Config(coordinator_address='h:1', num_processes=2,
             sdc_check=True, sdc_allgather=False))
  assert any('all-gather' in w for w in warnings), warnings
  # With the all-gather on (default) the sentinel runs: no warning.
  assert not any('all-gather' in w for w in validate_distributed(
      Config(coordinator_address='h:1', num_processes=2)))


def test_validate_distributed_tp_across_hosts_cross_link():
  warnings = validate_distributed(
      Config(coordinator_address='h:1', num_processes=2,
             model_parallelism=2))
  assert any('shard_batch_over_model' in w for w in warnings), warnings
  # Single-host TP: no cross-host predicate, no warning.
  assert not any('shard_batch_over_model' in w
                 for w in validate_distributed(
                     Config(model_parallelism=2)))


def test_validate_distributed_filler_cross_link():
  warnings = validate_distributed(
      Config(coordinator_address='h:1', num_processes=2,
             anakin_filler=True, surrogate='impact'))
  assert any('filler' in w for w in warnings), warnings


def test_validate_distributed_one_process_coordinator_warns():
  warnings = validate_distributed(
      Config(coordinator_address='h:1', num_processes=1))
  assert any('coordinates nothing' in w for w in warnings)
  warnings = validate_distributed(Config(process_id=1))
  assert any('coordinator_address' in w for w in warnings)


# --- staging arena: process_index slot placement ---------------------


class _FakeDevice:
  def __init__(self, did, process_index):
    self.id = did
    self.process_index = process_index

  def __repr__(self):
    return f'dev{self.id}@p{self.process_index}'


def test_unroll_slot_owners_single_process_contiguous():
  devs = [_FakeDevice(i, 0) for i in range(4)]
  owners = train_parallel.unroll_slot_owners(devs, 8)
  # Slot s -> local device s // per_dev: contiguous groups of 2 — the
  # data-axis shard layout batch_shardings assigns.
  assert [d.id for d in owners] == [0, 0, 1, 1, 2, 2, 3, 3]


def test_unroll_slot_owners_uses_only_local_devices():
  # The 2-process view of a 4-device mesh: this process owns devices
  # 2 and 3 only; its 4 local slots must map onto exactly those (the
  # process_index placement extension — trajectory data must never be
  # assigned another host's device).
  local = [_FakeDevice(2, 1), _FakeDevice(3, 1)]
  owners = train_parallel.unroll_slot_owners(local, 4)
  assert [d.id for d in owners] == [2, 2, 3, 3]
  assert all(d.process_index == 1 for d in owners)


def test_unroll_slot_owners_one_device_per_process():
  # The v5e-pod shape: 1 addressable device, the whole local batch on
  # it.
  local = [_FakeDevice(7, 3)]
  owners = train_parallel.unroll_slot_owners(local, 4)
  assert [d.id for d in owners] == [7, 7, 7, 7]


def test_unroll_slot_owners_indivisible_raises():
  devs = [_FakeDevice(i, 0) for i in range(3)]
  with pytest.raises(ValueError, match='does not divide'):
    train_parallel.unroll_slot_owners(devs, 4)
  with pytest.raises(ValueError, match='does not divide'):
    train_parallel.unroll_slot_owners([], 4)


def test_make_unroll_assembly_matches_slot_owner_arithmetic():
  """The real assembly (single process, real mesh) must agree with the
  pure arithmetic it now delegates to."""
  from scalable_agent_tpu.parallel import mesh as mesh_lib
  from scalable_agent_tpu.models.instruction import MAX_INSTRUCTION_LEN
  from scalable_agent_tpu.testing import make_example_batch
  n = jax.device_count()
  cfg = Config(batch_size=2 * n, unroll_length=2,
               num_action_repeats=1)
  mesh = mesh_lib.make_mesh(model_parallelism=1)
  batch = make_example_batch(3, cfg.batch_size, 24, 32, 3,
                             MAX_INSTRUCTION_LEN)
  slot_devices, _ = train_parallel.make_unroll_assembly(
      cfg, mesh, batch)
  expected = train_parallel.unroll_slot_owners(
      [d for d in mesh.devices.flat], cfg.batch_size)
  assert slot_devices == expected


# --- TP compute-mode resolution --------------------------------------


def test_resolve_tp_compute_auto_is_gathered_on_cpu():
  # The suite runs on the CPU backend (conftest pins JAX_PLATFORMS):
  # auto must take the gathered workaround there, and the explicit
  # values must win regardless of backend.
  assert jax.default_backend() == 'cpu'
  assert train_parallel.resolve_tp_compute(Config()) == 'gathered'
  assert train_parallel.resolve_tp_compute(
      Config(tp_compute='sharded')) == 'sharded'
  assert train_parallel.resolve_tp_compute(
      Config(tp_compute='gathered')) == 'gathered'


# --- distributed.maybe_initialize plumbing ---------------------------


def test_maybe_initialize_is_a_no_op_without_coordinator():
  assert distributed.maybe_initialize(Config()) is False


def test_maybe_initialize_is_a_no_op_when_already_joined(monkeypatch):
  calls = []
  monkeypatch.setattr(jax.distributed, 'is_initialized', lambda: True)
  monkeypatch.setattr(distributed, 'initialize',
                      lambda *a, **k: calls.append((a, k)))
  assert distributed.maybe_initialize(
      Config(coordinator_address='h:1', num_processes=2)) is False
  assert not calls


def test_maybe_initialize_resolves_process_id_from_task(monkeypatch):
  calls = []
  monkeypatch.setattr(jax.distributed, 'is_initialized', lambda: False)
  monkeypatch.setattr(
      distributed, 'initialize',
      lambda addr, num_processes, process_id: calls.append(
          (addr, num_processes, process_id)))
  assert distributed.maybe_initialize(
      Config(coordinator_address='h:1', num_processes=4, task=2)) is True
  assert calls == [('h:1', 4, 2)]
  # Explicit process_id wins over task.
  calls.clear()
  distributed.maybe_initialize(
      Config(coordinator_address='h:1', num_processes=4, task=2,
             process_id=3))
  assert calls == [('h:1', 4, 3)]
