"""Worker backend (jit): compiles and compile-cache loads the workers
counted inside the window, summed over workers.  The warm-up should
leave none."""


def read(run):
    return sum(1 for d in run.workers.values() for t in d["compiles"]
               if run.in_window(t))
