"""Serving, in two stacks that share nothing but this package name.

* ``serve.engine`` — the decode engine on the TPU stack: prefill and
  single-token decode programs over the model stack, and ``ServeEngine``
  for batched generation.
* The discrete-event serving simulator on the analytic Snitch-cluster cost
  models: arrival traces (``serve.traffic``), the event loop and service
  pricer (``serve.sim``), and autoscaling policies (``serve.policies``).

This module imports neither, so importing the engine loads no simulator
module and the simulator loads no JAX model code.  Import the module you
use.
"""
