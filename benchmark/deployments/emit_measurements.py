"""The deployment of upstream's ``emit_measurements`` topic: fixed-width JSON
records ``{occurred_at_ms, sensor_name, reading}`` and a window of
aggregates of ``reading`` by ``sensor_name``, tumbling or sliding, with an
optional ``>`` filter on one of its outputs.  Every configuration of the
benchmark names it.  It wraps ``harness/events.py`` (the seeded chunks),
``harness/wire.py`` (their bytes) and ``harness/reference.py`` (the f64
fold and its bfloat16 control).

A deployment module is what the harness takes from a configuration's
``module``, and all it takes from it:

- ``encoder_params(config, traffic, base)``: the parameters the feeder's
  encoder children get, as JSON.  ``base`` holds ``seed``, ``partitions``,
  ``chunk_ms`` and ``events_per_chunk``; the result keeps them.
- ``encode_chunk(params, c)``: chunk ``c`` as one Kafka record-batch blob a
  partition, ``events_per_chunk / partitions`` records each, made from
  ``default_rng([seed, c])``, covering event time ``[T0 + c*chunk_ms, T0 +
  (c+1)*chunk_ms)``.  The feeder keeps the chunks' timing and order.
- ``build_stream(config, bootstrap)``: ``(ctx, ds)`` through ``Context`` and
  ``DataStream`` as a user calls them.  The engine is imported inside it
  alone: the encoder children load this module and never touch JAX.
- ``kept_columns(config)`` and ``window_ms(config)``: the output columns the
  consumer keeps for the comparison, the group key first, and the window's
  ``(length_ms, slide_ms)``.
- ``check(config, params, start_ms, end_ms, kept, control)``: the plain
  reference over the windows that lie wholly inside ``[start_ms, end_ms)``
  held against ``kept`` = ``(window starts, {column: values})``, the rows
  delivered for them.  Returns ``(numbers, control numbers or None)``, each
  with ``rows_compared`` and ``bad_windows`` (windows with a row missing,
  unexpected or wrong); the control is the reference in the precision below
  the configuration's, put in the engine's place.
- ``LIMITS`` (optional): the limit of each number with a name that
  ``reference.LIMITS`` lacks.  A number with neither fails the run.
"""

from __future__ import annotations

import functools

from benchmark.harness import events, reference, wire

SAMPLE_JSON = '{"occurred_at_ms": 100, "sensor_name": "foo", "reading": 0.0}'


def encoder_params(config: dict, traffic: dict, base: dict) -> dict:
    """The fields of ``events.Feed``."""
    return {
        **base,
        "n_keys": config["keys"]["count"],
        "key_prefix": config["keys"]["prefix"],
        "records_per_batch": config["records_per_batch"],
    }


@functools.lru_cache(maxsize=4)
def _encoder(key_prefix: str, key_width: int, records_per_batch: int):
    return wire.Encoder(key_prefix, key_width, records_per_batch)


def encode_chunk(params: dict, c: int) -> list[bytes]:
    feed = events.Feed(**params)
    encoder = _encoder(feed.key_prefix, feed.key_width, feed.records_per_batch)
    ts, kid, micro = events.chunk_arrays(feed, c)
    per = feed.events_per_chunk // feed.partitions
    first_ts = events.T0 + c * feed.chunk_ms
    n = feed.partitions
    return [
        encoder.encode(c * per, first_ts, ts[p::n], kid[p::n], micro[p::n])
        for p in range(n)
    ]


def build_stream(config: dict, bootstrap: str):
    from denormalized_tpu import Context, col
    from denormalized_tpu.api import functions as F
    from denormalized_tpu.api.context import EngineConfig

    q = config["query"]
    ctx = Context(EngineConfig(**config.get("engine", {})))
    aggs = [
        getattr(F, kind)(col("reading")).alias(name)
        for name, kind in q["aggregates"]
    ]
    ds = ctx.from_topic(
        config["topic"], sample_json=SAMPLE_JSON, bootstrap_servers=bootstrap,
        timestamp_column="occurred_at_ms",
    )
    if q["slide_ms"] == q["length_ms"]:
        ds = ds.window(q["keys"], aggs, q["length_ms"])
    else:
        ds = ds.window(q["keys"], aggs, q["length_ms"], q["slide_ms"])
    if q.get("filter"):
        ds = ds.filter(col(q["filter"]["column"]) > q["filter"]["gt"])
    return ctx, ds


def kept_columns(config: dict) -> list[str]:
    q = config["query"]
    return [q["keys"][0]] + [name for name, _kind in q["aggregates"]]


def window_ms(config: dict) -> tuple[int, int]:
    q = config["query"]
    return q["length_ms"], q["slide_ms"]


def check(config: dict, params: dict, start_ms: int, end_ms: int, kept,
          control: bool = False) -> tuple[dict, dict | None]:
    q = config["query"]
    aggs = [tuple(a) for a in q["aggregates"]]
    flt = (q["filter"]["column"], q["filter"]["gt"]) if q.get("filter") else None
    ref = reference.Reference(
        events.Feed(**params), q["length_ms"], q["slide_ms"], start_ms, end_ms
    )
    ws, got = kept
    got = {**got, "cells": ref.cells_of(ws, got[q["keys"][0]].tolist())}
    numbers = reference.compare(ref, got, aggs, flt)
    if not control:
        return numbers, None
    bf16 = reference.rows_of(ref.bf16_fold(), ref, aggs, flt)
    return numbers, reference.compare(ref, bf16, aggs, flt)
