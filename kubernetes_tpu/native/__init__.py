"""Native (C) components.

- `_replay`: the wave-replay engine (pure C, loaded via ctypes from
  models/replay.py).
- `_kquantity`: resource-quantity parser fast path (CPython extension).
- `_ktlv`: the TLV wire codec (CPython extension, runtime/tlv.py).
- `pause.c` (under build/pause/): the pod sandbox placeholder binary,
  mirroring the reference's only C file (build/pause/pause.c).

The libraries are self-provisioning: `build.ensure_all()` compiles them
on demand whenever a C compiler is present, each into a file named by a
hash of its source and flags, so no manual build step is needed and a
library built from other source is never loaded. Without a compiler or
Python headers `_kquantity` is None and api/resource.py degrades to the
pure-Python parser.
"""

from kubernetes_tpu.native import build as _build

_kquantity = _build.load_extension("_kquantity")
