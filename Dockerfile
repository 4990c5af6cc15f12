# Headless bluesky_tpu server (reference parity: /root/reference/Dockerfile,
# docker-compose.yaml — the same "server in a container, clients connect
# over ZMQ" deployment).
#
#   docker build -t bluesky-tpu .
#   docker run -p 11000-11001:11000-11001 bluesky-tpu
#
# For TPU VMs, base on a jax[tpu] image instead and install with
# `pip install -e .[tpu]`.  Two things to know there: capacity is a
# setting (`nmax = 100000` in the file given as --config-file, which the
# server hands to the workers it spawns), and a worker that cannot get
# the chip stops (one worker per host; a process that was not put on
# the CPU by JAX_PLATFORMS=cpu and finds no accelerator raises at its
# first kernel).  Set JAX_COMPILATION_CACHE_DIR to a volume to keep
# compiled programs across container restarts.
FROM python:3.12-slim

WORKDIR /app

# Build tools only for the optional cgeo C extension
RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

COPY requirements.txt .
RUN pip install --no-cache-dir -r requirements.txt

COPY pyproject.toml README.md ./
COPY bluesky_tpu ./bluesky_tpu
COPY scenario ./scenario
RUN pip install --no-cache-dir -e . \
    && (cd bluesky_tpu/src_cpp && python setup.py build_ext --inplace || \
        echo "cgeo build skipped — NumPy host-geo fallback is automatic")

# Event/stream ports for clients, worker ports stay internal
EXPOSE 11000 11001

# Point at a BlueSky data checkout if you have one (docs/DATA.md):
#   docker run -v /path/to/bluesky/data:/data -e BLUESKY_TPU_DATA=/data ...
CMD ["bluesky-tpu", "--headless"]
