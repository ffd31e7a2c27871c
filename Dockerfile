# Deployment image for scalable_agent_tpu (build/deploy parity with the
# reference's Dockerfile — reference: Dockerfile ≈L1–50, which builds
# DeepMind Lab + TF1; here: JAX TPU + the C++ host batcher).
#
# Build:  docker build -t scalable-agent-tpu .
# Train:  docker run --privileged scalable-agent-tpu \
#           python experiment.py --mode=train --level_name=dmlab30
#
# TPU access requires the libtpu runtime of the host VM (Cloud TPU VMs
# mount it automatically with --privileged); for CPU-only smoke runs no
# flags are needed (env_backend=fake/bandit).
#
# DeepMind Lab / ALE are NOT baked in (they are external native
# dependencies exactly as in the reference); install them in a derived
# image and the import-guarded adapters (envs/dmlab.py, envs/atari.py)
# pick them up.

FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
      g++ make && \
    rm -rf /var/lib/apt/lists/*

# TPU-enabled JAX + the framework's python dependencies.
RUN pip install --no-cache-dir \
      "jax[tpu]" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html \
      flax optax orbax-checkpoint chex einops numpy absl-py pytest

WORKDIR /app
COPY scalable_agent_tpu/ scalable_agent_tpu/
COPY tests/ tests/
COPY scripts/ scripts/
COPY docs/ docs/
COPY benchmark/ benchmark/
COPY experiment.py chip_smoke.py BENCHMARK.json __graft_entry__.py README.md LICENSE ./

# Native host batcher (ctypes; no TF/pybind dependency).
RUN make -C scalable_agent_tpu/ops/batcher

# Smoke-verify the image: unit tests on a virtual CPU mesh.
RUN python -m pytest tests/test_vtrace.py tests/test_dynamic_batching.py -q

ENTRYPOINT []
CMD ["python", "experiment.py", "--helpshort"]
