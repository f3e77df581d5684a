# Container image for the TPU-native framework (ref reference Dockerfile:
# the reference bundles Spark + PIO; here the runtime is Python 3.12 +
# jax/jaxlib 0.9.0 + libtpu 0.0.34, which `pip install .` pulls in through
# pyproject's jax[tpu]==0.9.0). On a TPU host run it with the chip's
# devices passed through (/dev/vfio); train and deploy then need the chip
# and fail without one. With JAX_PLATFORMS=cpu the image serves the
# event/query/admin planes and runs the tests on the CPU.
FROM python:3.12-slim

RUN apt-get update \
 && apt-get install -y --no-install-recommends g++ curl \
 && rm -rf /var/lib/apt/lists/*

WORKDIR /opt/pio
COPY pyproject.toml README.md ./
COPY predictionio_tpu ./predictionio_tpu
COPY native ./native
COPY conf ./conf
COPY pio ./pio

RUN pip install --no-cache-dir . flax optax

# the package is installed, not run from a checkout, so its default cache
# path (<checkout>/.jax_cache) would land in site-packages: keep compiled
# programs in the data volume instead
ENV PIO_FS_BASEDIR=/var/lib/pio \
    JAX_COMPILATION_CACHE_DIR=/var/lib/pio/jax_cache
VOLUME /var/lib/pio

# event server 7070, engine server 8000, admin 7071, dashboard 9000
EXPOSE 7070 8000 7071 9000
ENTRYPOINT ["./pio"]
CMD ["eventserver", "--ip", "0.0.0.0"]
