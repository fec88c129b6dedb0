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
    Session(traffic, dep, seed, proc, n_connections)
                        optional: frames whose bytes depend on the replies
                        the same generator has read (a release that names
                        its acquire's token, a report of admitted calls
                        only). One per generator process; it may return
                        None where the traffic file asks for none
        encode(ci, xid, *cols_of_a_frame) -> bytes
                        at send time, on the sender's thread, in
                        ``encode_batch``'s place (``ci``: the connection)
        back(ci, xid, cols_of_the_frame, reply_rows, t)
                        on the connection's reader thread, before the
                        ledger counts the rows; ``reply_rows`` in the whole
                        ``BATCH_REPLIES`` row layout, as many as came
        lost(ci, xid)   optional: no reply will come (the frame was
                        skipped, timed out, or its connection died)

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

A family without ``Session`` has every frame of an open loop encoded before
the window (``traffic.encode_frames``) and ``encode_batch`` called in the
closed loop, as before there were sessions. With one, ``loadgen`` keeps the
schedule, the xids, the in-flight window, the failure accounting, the
latency and the ledger, and asks the session for a frame's bytes right
before it sends them (warm-up and bursts too: a session is warm when the
window starts); the send lag is then stamped after the encode, so
``client.send_lag_p99_ms`` holds what the session costs. The rows a frame is
counted by are the ``Mix``'s; what a session puts in front of them (a
report, a run of releases) is bytes the ledger does not count. One
connection's ``encode`` and ``back`` run on different threads in the open
loop: a session locks what they share. ``msg: single`` with a session raises
when the generator is built.

``flow.py`` (with ``flow_reference.py``) is the first family; ``hotparam.py``,
``shaped.py`` and ``breaker.py`` are the other three ``BENCHMARK.json`` uses,
and none of its cells runs a session (``breaker.py`` has one, built only for
a traffic file that states ``"reports": "admitted"``).
``tests/extra/families/paramflow.py`` and ``semaphore.py`` are fixtures: the
first proves the seam on the program as it is, the second the session (token
ids a reply carries, released in front of a later frame).
"""
