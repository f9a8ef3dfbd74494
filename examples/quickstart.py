"""Quickstart: the REXA VM in five minutes.

    PYTHONPATH=src python examples/quickstart.py

Covers: compiling a text code frame (active message), running it on the
jitted interpreter, the fixed-point DSP words, incremental code updates,
and checkpoint/restore (stop-and-go).
"""

import numpy as np

from repro.config import VMConfig
from repro.core.vm import REXAVM
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = VMConfig(cs_size=8192, steps_per_slice=2048)
    vm = REXAVM(cfg, backend="jit")   # "oracle" for the pure-Python twin

    print("== arithmetic & control flow ==")
    res = vm.eval(': fib dup 2 < if drop 1 else dup 1 - fib swap 2 - fib + endif ; 10 fib . cr')
    print(res.output.strip(), f"({res.steps} VM instructions)")

    print("== fixed-point DSP (paper Tab. 4; x/y scale 1:1000) ==")
    res = vm.eval('." sigmoid(1.0)=" 1000 sigmoid . cr ." sin(pi/2)=" 1571 sin . cr')
    print(res.output.strip())

    print("== vector ISA: a 2-layer ANN in one code frame (paper Ex. 2) ==")
    res = vm.eval(
        "array x { 500 -200 300 } "
        "array w { 10 -5 3 2 0 1 } array b { -4 5 } array s { -4 -4 } "
        "array h 2 "
        "x w h s vecfold h b h 0 vecadd h h 0 0 vecmap "
        '." activations: " h vecprint cr ." class: " h vecmax . cr'
    )
    print(res.output.strip())

    print("== incremental update (active message replaces a word) ==")
    vm.run(vm.load(": classify 100 * ; export classify"))
    print("v1:", vm.eval("3 classify .").output.strip())
    vm.run(vm.load(": classify 200 * ; export classify"))
    print("v2:", vm.eval("3 classify .").output.strip())

    print("== stop-and-go checkpointing ==")
    frame = vm.load("0 1000 0 do 1+ loop .")
    vm.launch(frame)
    vm._slice(512)                      # partial run ("power loss" now)
    ckpt = vm.checkpoint()
    vm2 = REXAVM(cfg, backend="jit")    # "reboot"
    vm2.restore(ckpt)
    res = vm2.run(max_slices=100)
    print("resumed result:", res.output.strip())


if __name__ == "__main__":
    main()
