from repro.compile_cache import enable_compile_cache
from repro.workloads.cli import main

enable_compile_cache()
main()
