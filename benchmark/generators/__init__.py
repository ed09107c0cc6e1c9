"""A configuration's stream, made by the generator its file names.

A configuration defines its stream with three keys: ``fields`` (the
schema, ``[[name, type], ...]``), ``stream`` (its name in the query;
``inputStream`` where the file gives none) and ``generator``, which
names ``generators/<generator>.py``. The generator's parameters are the
configuration's own keys. No file here imports the program.

The contract. The module has one function,

    make_pool(seed, n, cfg) -> pool

and the pool, made once a run from ``--seed``, answers with numpy arrays
and no Python per event:

``pool.n``
    the period: the random draws of event ``i`` are those of event
    ``i % n``. Only the draws repeat; whatever the generator derives
    from the event number (timestamps, ids that grow) runs on.
``pool.columns(lo, hi, names=None)``
    ``{field: array}`` of stream events ``lo <= i < hi``, for any global
    ``i``, in the dtypes the fields state (all of ``fields``, or
    ``names``). What references and tests read.
``pool.server(batch, intern)``
    ``serve``, where ``serve(j)`` is ``({field: array}, timestamps)`` of
    the stream's ``j``-th batch (pool batch ``j % (n / batch)`` in cycle
    ``j // (n / batch)``) with everything that changes between cycles
    applied: equal to ``columns(j * batch, (j + 1) * batch)``, a string
    field as the codes ``intern(field, value)`` gives. This is the
    timed path: views and one vectorised pass per changing column.
``pool.ts_of(i)``, ``pool.index_of(ts)``
    the event clock, both ways, on scalars and arrays. ``ts_of`` is
    non-decreasing. ``index_of(ts)`` is the last event whose timestamp
    is ``<= ts`` (-1 before the first): a row stamped with its window's
    end maps to the last event that could have contributed to it.

Several events may share a timestamp (a tick). The sink
(``bmlib/sink.py``) then counts a delivery as complete only through the
last event of the tick before its newest row's, because the rest of that
tick may still be to come; a tick that holds one event is complete with
its row. So "complete through event i" never overstates, and on a clock
with one event a tick (``uniform``) nothing changes.
"""
