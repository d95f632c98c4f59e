"""kubernetes_tpu.serve — arrival-driven serving (ROADMAP item 2).

Every headline number before round 16 was "drain a pre-built backlog";
serving heavy traffic means pods *arrive* — over the apiserver, through
informers, forever — and the scheduler must never idle the device OR let
an unbounded queue eat the startup SLO. This package turns the burst
pipeline into a serving system:

- `loop.ServeLoop` cuts fused drain windows from the LIVE activeQ on a
  cadence instead of draining to empty, reusing the shell's
  `schedule_burst` / `schedule_burst_fused` machinery unchanged so
  per-window decisions stay oracle-parity (the serve parity fuzz pins a
  ServeLoop's decision stream bit-identical to a serial oracle observing
  the same arrivals at window boundaries).
- `backpressure.BackpressureGate` is the explicit load-shedding contract:
  pod creates are checked against the activeQ-depth watermark at the
  store/apiserver admission surface and shed with
  429 + Retry-After (`store.BackpressureError`); `RemoteStore` honors the
  Retry-After with capped jittered backoff. Accepted creates stamp the
  lifecycle ledger's admission slot, so `pod_startup_seconds_p99` scores
  true accepted-create -> commit latency under arrival load.
- `arrivals.ArrivalGenerator` is the hollow arrival client: paced pod
  creation at a target rate against any Store surface (embedded or
  remote), honoring 429 sheds exactly like a well-behaved client.

A window is one launch: `core.tpu_scheduler` runs each as ONE dispatch and
ONE packed fetch (`TPUScheduler._launch`), the serve loop pins a uniform
launch's chunk to its window size (`TPUScheduler.launch_cap`), and a
refused or failed window decides nothing past its committed prefix: the
shell replans from the packed-block boundaries.
"""
from kubernetes_tpu.serve.backpressure import BackpressureGate  # noqa: F401
from kubernetes_tpu.serve.loop import ServeLoop                 # noqa: F401
from kubernetes_tpu.serve.arrivals import ArrivalGenerator      # noqa: F401
