"""Drivers: one module per served path of the program, found by the
``driver`` key of a configuration file (``chipbench/drivers/<driver>.py``).

The harness (``run.py``, ``readings.py``, ``sweep.py``) and the
benchmark's tests know nothing of a path but what this contract names, so
a cell served by a new driver adds its module and data files, and edits
no file that is already there.

A driver module exports:

``System(cfg, mix, devices)``
    The served path of configuration ``cfg`` (the file, as a dict) for
    traffic mix ``mix``, built on ``devices`` (the cell's chips), weights
    made on the device.  It has:

    * ``cfg``, ``lanes`` (requests served at once, over all chips),
      ``buckets`` (the shapes the mix reaches, which ``run.warm`` warms:
      ``cfg["buckets"]`` holds every bucket the driver has),
      ``reference_s`` (seconds of the build spent in the plain reference,
      which set-up leaves out);
    * ``step_program``: a regular expression for the name of the fused
      step's program on the device trace's "XLA Modules" line, which
      ``step_device_ms`` and the metrics built on it read;
    * ``submit(uid, toks, answer_len=None)``: queue a request of prompt
      ``toks`` (int32 token ids).  ``answer_len`` is given only where the
      traffic file states ``answer_lengths``: the tokens to serve.  A
      driver that takes it keeps it with the request, and its ``check``
      holds each answer to it;
    * ``step()``: one fused step; a label of what it stepped (the
      classifier's bucket), passed back to ``step_cost``, or ``None`` when
      nothing is left to do;
    * ``poll()``: the requests finished since the last poll, as
      ``[(uid, answer)]``; ``queued``: requests not yet finished;
    * ``counters()``: the program's counters, as a dict of numbers;
    * ``close()``: drop the served path, so that the reference can run;
    * ``step_cost(label)``: (operations, bytes) of one fused step on one
      chip, and ``answer_flops(toks, answer)``: the model operations the
      request needed;
    * ``check(prompts, answers)``: the comparison that decides
      ``correct``, as ``{name: (value, limit)}``;
    * ``answer_errors(prompts, answers)`` and
      ``control_answers(prompts, answers, precision)``: each answer's gap
      from the reference, and the control's answers to the same prompts,
      which a decoder reads at the served tokens' positions
      (``readings.py``);
    * ``notes()``: the run's facts that are particular to the driver, as a
      small dict of name -> value (the classifier's entropy threshold),
      printed by ``run.py`` and recorded by ``readings.py``.

``SMOKE``
    The configuration keys, and their values, at which a CPU runs the cell
    in seconds (``chipbench/tests/smoke.py``); a dict value updates the
    group of that name where the configuration has it.

``FAULTS``
    ``{name: plant}``: each ``plant(monkeypatch, cfg)`` breaks the driver's
    own timed path underneath, as ``chipbench/tests/test_faults.py``
    requires a run to read not correct with it: a step that returns its
    state unchanged, part of the batch left out, an answer altered where
    it is produced.

``compile_programs(cfg, mix, devices, chips)``
    Lowers and compiles, at the configuration's full widths, every program
    that the mix warms, for the first ``chips`` of ``devices`` (which may
    be described, not attached), and returns the compiled programs; raises
    where a program lacks what the configuration states.
"""
