"""The plain references of the output check, one per configuration.

``bench/reference/<config>.py`` is the configuration's own reference
where it brings one; every other configuration runs the shared
``cycle.py`` (``bench.cells.load_reference``). Both are loaded by path
from the checkout the harness was given. A configuration's module may
subclass the shared class, which ``bench.cells.shared_reference(root)``
gives, with ``root`` the ``bench`` directory the module lies in
(``Path(__file__).resolve().parents[1]``), and replace what differs:
the env, ``render``, ``param_shapes``, ``logits``, the replay,
``opt_update`` or the ``cycle``.

What the harness (``harness.reference_runs``) and ``bench/calibrate.py``
ask of a module's ``Reference``:

* ``Reference(cfg, envs, dtype)``: ``cfg`` the configuration file's
  dict, ``envs`` the env streams of one replica, ``dtype`` float32 for
  the reference proper (every matmul and convolution at
  ``Precision.HIGHEST``) and bfloat16 for the lower-precision control
  that ``calibrate.py`` runs;
* ``init(seed) -> state`` and ``cycle(state) -> (state, loss)``, the
  loss the cycle's mean, each a pure function of its argument that can
  be vmapped over seeds and jitted;
* ``state.params``, a dict whose leaves are named as those of the
  program's ``carry.params``, and ``state.opt``, a dict that holds the
  optimizer's first moment under the key that ``harness._moment``
  reads (RMSProp's ``g``, Adam's ``m``), named leaf for leaf as the
  params.

``compare.py`` turns a reference run and the program's into the numbers
that decide ``correct``. No module here imports anything from the
program (``src/repro``) or takes anything the program has made.
"""
