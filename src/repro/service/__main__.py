"""``python -m repro.service``: start the live traffic emulation service.

Flags mirror the ``repro.scenarios serve`` subcommand; the HTTP routes
are documented in :mod:`repro.service.http`.
"""
import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.service.http import serve


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m repro.service",
        description="HTTP load-run service over the emulation fleet")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8787,
                    help="0 picks a free port (printed at startup)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    serve(args.host, args.port)


if __name__ == "__main__":
    main()
