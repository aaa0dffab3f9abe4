# Mirror of the reference's Dockerfile role (reference Dockerfile:1-100
# bakes the emit_measurements data generator into a Kafka broker image so
# `docker run -p 9092:9092 emgeee/kafka_emit_measurements` gives examples a
# live feed, README.md:95-98).  Here the embedded wire-compatible mock
# broker plays the broker part and the same generator feeds it:
#
#   docker build -t denormalized-tpu-kafka .
#   docker run --rm -p 9092:9092 denormalized-tpu-kafka
#   # then, on the host:
#   python examples/simple_aggregation.py --bootstrap-servers localhost:9092
#
# The image also carries the full framework (CPU JAX), so it doubles as a
# reproducible environment for the test suite:
#   docker run --rm denormalized-tpu-kafka python -m pytest tests/ -q
FROM python:3.12-slim

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY denormalized_tpu ./denormalized_tpu
COPY examples ./examples
COPY tests ./tests

RUN pip install --no-cache-dir -e .[dev] "jax[cpu]==0.9.0"
# pre-build the native components (each falls back to pure Python at
# runtime if compilation is impossible, hence the permissive tail on
# THIS step only — a failed pip install above still fails the build)
RUN python -c "from denormalized_tpu.native.build import load; \
[load(m) for m in ('kafka_client', 'lsmkv', 'partial_agg', \
'json_parser', 'avro_parser', 'interner')]" \
    || true

ENV JAX_PLATFORMS=cpu
EXPOSE 9092
CMD ["python", "examples/emit_measurements.py", "--port", "9092", "--host", "0.0.0.0"]
