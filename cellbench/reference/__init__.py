"""Copies of the program's plain float32 references, kept with the
benchmark: its files import no program file that a later PR can change
(tests/cellbench/test_cellbench_qwen3_next.py holds each copy to its
original, byte for byte)."""
