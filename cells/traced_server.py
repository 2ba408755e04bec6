"""The traced run's deployment: the program's ``LLMServer`` class plus a
way to start and stop the JAX profiler in the replica's own process (only
the process that holds the chip can trace it, and ``ray_tpu/`` has no hook
for one yet: PERF.md, Open questions).  It adds nothing else, and the
untraced run never sees it.
"""


def traced(server_cls):
    class TracedLLMServer(server_cls):
        def start_profile(self, trace_dir: str) -> bool:
            import jax

            jax.profiler.start_trace(trace_dir)
            return True

        def stop_profile(self) -> bool:
            import jax

            jax.profiler.stop_trace()
            return True

    TracedLLMServer.__name__ = server_cls.__name__
    TracedLLMServer.__qualname__ = server_cls.__qualname__
    return TracedLLMServer
