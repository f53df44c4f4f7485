"""One module per model kind: ``bench/models/<kind>.py`` serves every
configuration whose ``model.kind`` is ``<kind>``.  It gives

- ``PROGRAM``: the model name the program's ``rounds.FLApp`` trains;
- ``shapes(model) -> {leaf path: shape}``: the trained leaves, from
  which ``Spec.n_params`` is counted and the priced ``model_bytes`` is
  held to; only these go on the wire;
- ``init_params(key_seed, model, n_apps) -> [params per app]``: every
  app's weights, drawn on the device in one jitted call from
  ``key_seed``, in the type they are stored in;
- ``app_data(seed, app, config, workers) -> [shard per worker]``: one
  app's data, drawn from the run's ``seed`` and ``app`` (under
  ``fixture.DATA``); a shard is a pytree of arrays, the sample axis
  first, as the program's ``FLApp.data`` takes it;
- ``loss(params, batch, *, mm, dtype) -> scalar``: the plain
  ``jax.numpy`` loss of one shard, for the reference.  Every matmul goes
  through ``mm``, so the reference's controls can round its operands;
  input leaves that are floats are cast to ``dtype``.  It imports
  nothing of the program;
- ``train_flops(model, config) -> int``: the operations of the local
  training behind one commit, for ``train_mfu``;
- ``shrink(model, **sizes) -> model``: the model at the size the CPU
  rehearsals in ``tests/bench`` run.

A kind whose apps share a frozen part of the model (a base under
trained adapters) also gives

- ``shared(key_seed, model) -> pytree``: the frozen weights, drawn once
  per deployment on the device in one jitted call (under
  ``fixture.SHARED``), in the type the configuration states.  Every
  app's model reads the one copy; no commit, aggregate or broadcast
  carries it, and ``shapes`` leaves it out;
- ``program_fields(shared) -> dict`` (optional): keyword arguments the
  program's ``rounds.FLApp`` takes to reach the frozen weights, given to
  every app; none where the kind leaves it out;

and its ``loss`` takes them as ``loss(params, batch, *, mm, dtype,
shared)``: only ``params`` is differentiated, every matmul with a frozen
weight still goes through ``mm``, and float leaves of ``shared`` are
cast to ``dtype`` as inputs are.  A kind without ``shared`` is called
with the four arguments above, as before.

Nothing else in the benchmark knows the model; ``bench.lib.spec.model_kind``
finds the module by name."""
