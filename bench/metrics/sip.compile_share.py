"""Share of the SIP search's seconds spent in JAX tracing, lowering and
compiling (%): the tuner's ``tune.compile_s`` counter, to which
``SipKernel.tune`` adds the compile seconds of each call in the process's
active metrics registry, over the search's seconds on the host clock.  The
tuning cell runs no tuning outside its search.  A program without the
counter gives nothing."""


def read(w):
    from repro.obs import metrics
    reg = metrics.active_registry()
    if "tune.compile_s" not in reg or not w.search_s:
        return None
    return 100.0 * reg.counter("tune.compile_s").value / w.search_s
