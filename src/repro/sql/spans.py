"""Host spans and dispatch counters of the query path.

Each span is a ``jax.profiler.TraceAnnotation``: it lands in the
profiler's trace on the same clock as the device's ops, and costs a
name check when no profiler runs (the ``rid=`` metadata is encoded only
while one does).  The server runs one request at a time on one thread,
so a child span belongs to the request span that contains it in time.

    sql.query      one request through the degradation ladder
    sql.wave       one shared pass over the fact table
    sql.plan       validation and strategy choice
    sql.hashtable  a dimension hash table: lookup, or build on a miss
    sql.upload     one host-to-device copy (``storage.upload``)
    sql.dispatch   a jitted kernel call, up to its asynchronous return
    sql.pull       the result to the host: the wait on the device and
                   the device-to-host copy
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span

QUERY = "sql.query"
WAVE = "sql.wave"
PLAN = "sql.plan"
HASHTABLE = "sql.hashtable"
UPLOAD = "sql.upload"
DISPATCH = "sql.dispatch"
PULL = "sql.pull"

# process-wide dispatch counters (reset via compile.reset_launch_stats):
# "probe" counts probe-kernel dispatches and "partition" radix-shuffle
# passes on the join probe path, the overhead axis fig8 attributes the
# fused-vs-loop win to; "host_syncs" counts device->host round-trips of
# probe-side arrays (the loop path's other hidden cost); "uploads" and
# "upload_bytes" count the host->device copies of ``storage.upload``;
# "streams" counts ``storage.column_stream`` calls and "streams_resident"
# those served by a column buffer already on the device.
LAUNCH_STATS = {"probe": 0, "partition": 0, "host_syncs": 0,
                "uploads": 0, "upload_bytes": 0,
                "streams": 0, "streams_resident": 0}

