# Serving runtime image for gofr_tpu (built by the `docker` CI job;
# asserted by tests/test_ci_config.py).
#
# Pinning discipline for TPU hosts: jax, jaxlib, and libtpu MUST move in
# lockstep — a libtpu from a different release than jaxlib produces
# undefined runtime behavior, not a clean error. The pins live in the two
# build args below; bump them TOGETHER and only to combinations published
# on the jax release matrix:
#
#   JAX_VERSION    the jax/jaxlib release (0.9.0 pairs with libtpu 0.0.34 — the
#                  installation chip_smoke.py was brought up on)
#   JAX_EXTRAS     ""      → CPU-only image (CI builds this: hermetic,
#                            no TPU wheel downloads)
#                  "[tpu]" → pulls the matching libtpu via the release
#                            index (requires network access to
#                            storage.googleapis.com at build time)
#
# On a TPU VM, run with --privileged --net=host (the TPU driver is host-
# side; /dev/accel* must be visible) and set TPU_MESH for the topology.
#
#   docker build -t gofr-tpu-serving .
#   docker build -t gofr-tpu-serving --build-arg JAX_EXTRAS="[tpu]" .
#   docker run --rm -p 8000:8000 -p 2121:2121 gofr-tpu-serving

FROM python:3.12-slim

ARG JAX_VERSION=0.9.0
ARG JAX_EXTRAS=""
# the libtpu release index the [tpu] extra resolves against; pinned so an
# image rebuild months later still gets the SAME libtpu for this jaxlib
ARG LIBTPU_INDEX=https://storage.googleapis.com/jax-releases/libtpu_releases.html

WORKDIR /srv/gofr_tpu

RUN pip install --no-cache-dir \
        "jax${JAX_EXTRAS}==${JAX_VERSION}" \
        -f "${LIBTPU_INDEX}" \
        flax optax orbax-checkpoint chex einops numpy \
        aiohttp httpx transformers grpcio protobuf cryptography pyyaml

COPY gofr_tpu ./gofr_tpu
COPY examples ./examples
COPY jaxpin.py pyproject.toml ./

ENV PYTHONUNBUFFERED=1
# HTTP / metrics / gRPC (docs/configs.md)
EXPOSE 8000 2121 9000

# default entrypoint: the LLM serving example (random-init dev weights);
# real deployments override CMD with their own app module
CMD ["python", "examples/serving-llm/main.py"]
