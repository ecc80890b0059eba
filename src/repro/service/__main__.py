"""``python -m repro.service`` — run the standalone DSE daemon.

Preferred over ``-m repro.service.server`` (which works too, but trips
runpy's already-imported warning because the package imports the server
module at import time).
"""
from ..launch.compile_cache import use_compile_cache
from .server import main

if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
