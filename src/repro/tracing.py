"""Program spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``swapnet.<name>``: it lands in a ``jax.profiler`` trace on the thread that
opened it, nested in whatever span that thread has open, with ``args`` as
the event's arguments (``step=``, ``rid=``, ``unit=``, ``block=``). With no
profiler session open it records nothing and costs about a microsecond, so
the hot path carries its spans unconditionally: the profiler session is the
only switch.

Spans, by thread (each nests in the one above it):

  executor   ``decode_step`` (step) > ``exec`` (block) > ``exec.sync``,
             ``kv.write`` (the dispatch of a layer's K/V append),
             ``exec.experts`` (layer, group: one expert-group unit's
             program);
             ``admit`` (rid) > ``prefill`` > ``exec`` > ...;
             ``swap.wait`` and ``swap.out`` between the blocks of a pass
  loader     ``swap.read``, ``swap.unpack``, ``swap.dispatch`` (unit)

Counters (bytes, seconds, counts) live on the objects that own them
(``SwapStats``, ``PagedKVCache``, ``BatchDecodeEngine.stats()``); the
expert-group counters ``experts_routed`` (on the device) and
``experts_streamed`` on ``SwappedModel``, read through ``stats()``.
"""
from __future__ import annotations

import jax

__all__ = ["span"]


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``swapnet.<name>`` carrying ``args``."""
    return jax.profiler.TraceAnnotation("swapnet." + name, **args)
