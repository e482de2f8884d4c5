"""ceph_tpu — a TPU-native distributed-storage framework with Ceph's capabilities.

Built from scratch on JAX/XLA (compute path) + C++ (native runtime), not a
port of the reference's C/C++ design.  The flagship subsystem is erasure coding:
a ``plugin=tpu`` Reed-Solomon GF(2^8) backend whose parity math runs as a
bit-plane GF(2) matmul on the TPU MXU, registered through the same pluggable
codec-registry architecture the reference uses (see
/root/reference/src/erasure-code/ErasureCodePlugin.h:24-79).

Layout:
  ceph_tpu.ec        codec interface, registry, GF math, CPU codecs, tpu plugin
  ceph_tpu.ops       JAX/XLA kernels (bit-plane GF matmul and friends)
  ceph_tpu.parallel  device mesh, shardings, distributed EC service
  ceph_tpu.rados     mini-RADOS: messenger, monitor, OSD, EC backend, stores
  ceph_tpu.utils     buffers, profiles, config, perf counters, logging
"""

__version__ = "0.1.0"

# Plugin ABI version handshake, mirroring the reference's __erasure_code_version
# check against CEPH_GIT_NICE_VER (ErasureCodePlugin.cc:120-178): a plugin built
# against a different version is refused with -EXDEV.
PLUGIN_ABI_VERSION = __version__
