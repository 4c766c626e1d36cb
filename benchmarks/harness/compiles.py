"""Count what a process builds while it runs."""


class CompileCounter:
    """Programs this process built, from JAX's own monitoring events:
    ``n`` counts every new program (compiled, or loaded from the
    persistent cache - either stalls the request that needed it), and
    ``hits`` those the persistent cache supplied."""

    def __init__(self):
        import logging
        import re

        import jax
        self.n = 0
        self.hits = 0
        self.names = []     # what was built, newest last (bounded)
        pat = re.compile(r"Finished XLA compilation of (.+?) in ")
        counter = self

        class Names(logging.Handler):
            def emit(self, record):
                m = pat.match(record.getMessage())
                if m:
                    counter.names = (counter.names + [m.group(1)])[-50:]
        # JAX logs the name at DEBUG on this logger (devmon, which the
        # engine installs, already sets it to DEBUG without propagation)
        dlog = logging.getLogger("jax._src.dispatch")
        dlog.addHandler(Names(level=logging.DEBUG))
        if dlog.getEffectiveLevel() > logging.DEBUG:
            dlog.setLevel(logging.DEBUG)
            dlog.propagate = False

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
