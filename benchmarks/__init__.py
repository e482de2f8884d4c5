"""The benchmark of tpu-ceph's served path.  See PERF.md."""
