"""Serving example: the durable request/completion spine around a
crash-safe completion registry (a SOFT DurableMap on the bucket backend,
i.e. the Pallas hash_probe lookup / recovery_scan recovery path), with a
crash drill that proves no acknowledged request is lost.

Run:  PYTHONPATH=src python examples/serve_kv.py
"""
from repro.launch import serve as S


def main():
    S.main(["--requests", "8", "--crash", "--backend", "bucket", "--queue"])


if __name__ == "__main__":
    main()
