"""Rule families. A family is everything the harness knows about what a
rule, a row and a frame are; ``run.py``, ``loadgen.py`` and ``probe.py`` keep
the timing, the failure accounting, the void windows and the stall watch, and
call the family wherever they would have to know.

A configuration file names its family with ``"family": "<name>"`` (absent:
``"flow"``), and ``deploy.family`` finds ``<a path>/families/<name>.py`` in
the directories of ``paths``, as ``manifest.Cell`` finds a reader. One module
(it may import helpers that sit beside it) with these names:

JAX-free, numpy only (the generator processes import it):

    Deployment(spec)    the deployment built from the file's JSON, with
                        ``name``, ``spec``, ``window_ms``, ``bucket_ms`` and
        ledger_counts()                     the count of every ledger key
                                            (one row of the admitted table)
        ledger_view(cols, st, remaining)    ``(decided, brownout_pass,
                                            n_never, keys, tokens)`` for rows
                                            that came back: two masks, a
                                            count of rows that can never be,
                                            and the keys under which admitted
                                            tokens are summed
        window_checks(client)               ``[(what, got, limit)]`` over the
                                            merged ledger of a window
    Mix(traffic, dep, seed, salt)           ``frame_rows``,
        frame_tenants(n), rows(who),        rows as a tuple of columns, each
        frames(n)                           ``[n_frames, frame_rows, ...]``
    encode_batch(xid, *cols_of_a_frame)     bytes of one batch frame
    encode_singles(first_xid, *cols)        one-row frames as one packed
                                            array with an ``xid`` field:
                                            ``arr[i:j].tobytes()`` is ready
    SINGLE_REPLIES      ``(type bytes, frame dtype)`` of the one-row replies
                        ``wire.Splitter`` returns rather than skips (fields
                        ``xid``, ``status``, ``remaining``, ``wait_ms``)
    BATCH_REPLIES       ``(type bytes, row dtype)`` of the batch replies
    MAX_ROWS_PER_FRAME  the most rows one batch frame carries
    probe_checks(probe) the probe's sets: callables that send rows through
                        ``probe.send(*cols)``, compare every verdict with the
                        family's plain reference and ``probe.record(name,
                        rows, mismatches, seconds)``

With the program (the server process only; imports inside the functions):

    service_args(dep)   constructor arguments of ``DefaultTokenService`` the
                        file states
    load_rules(service, dep)    loads the rules through the service's public
                        entry, checks their number, returns it
    drive_before_window(built, traffic, dep, seed, compiles, say)
                        what is driven in process before the window; returns
                        the fused depths the lane must then be seen to reach
    progress(built)     a zero-argument count of finished work, which the
                        stall watch expects to keep rising in a window
    CONTROLS            ``{name: wrap_service}``: the controls of
                        ``control.py``, each a broken guarantee

``flow.py`` (with ``flow_reference.py``) is the first family and the only one
``BENCHMARK.json`` uses; ``tests/extra/families/paramflow.py`` is a second,
kept as a fixture that proves the seam on the program as it is.
"""
