# TPU-ready image for perceiver-io-tpu (reference: Dockerfile — pytorch/cuda
# runtime + poetry; here a JAX TPU runtime + pip install).
FROM python:3.12-slim

WORKDIR /app
COPY pyproject.toml README.md ./
COPY perceiver_io_tpu ./perceiver_io_tpu

# The versions are the ones pyproject.toml pins (jax 0.9.0, flax 0.12.3,
# optax 0.2.6, orbax-checkpoint 0.11.32). On a TPU VM replace the first line with:
#   pip install "jax[tpu]==0.9.0"
RUN pip install --no-cache-dir jax==0.9.0 \
    && pip install --no-cache-dir .[text,vision,audio,test]

ENTRYPOINT ["python", "-m"]
CMD ["perceiver_io_tpu.scripts.text.clm", "--help"]
