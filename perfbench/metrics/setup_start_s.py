from setupparts import part

META = {
    "name": "setup_start_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "compile and shape ladder",
    "moves": "setup_s",
    "what": "the process ledger's `backend_ready_unix` less its "
            "`start_unix`: process start (the kernel's record) to the "
            "moment the program first saw JAX's backend up, which in this "
            "harness is the entry of `build_model`: the interpreter, the "
            "imports, JAX, the TPU backend and the harness's few steps "
            "before the job is built.  Nothing to read on a record without "
            "`process` or in a rehearsal",
}


def read(ctx):
    return part(ctx, "start")
