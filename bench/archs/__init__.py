"""Architectures: one module per network layout, found by name.

A configuration file (``configs/<name>.json``) names its architecture
under ``"arch"``; ``spec.cell`` loads ``archs/<arch>.py`` from beside
it.  Nothing else in ``bench/`` knows a layer, a kernel size or a block
count: a configuration with a new layout is a new module here plus its
configuration file.  A module gives four functions:

``program_spec(cfg)``
    The program's spec object for ``plan.build_plan``.  The only
    function that imports the program (``repro``), and it does so in
    its body.
``weights(key, cfg) -> (params, state)``
    float32 pytrees from the PRNG ``key``, made on the device in one
    jitted call, in the program's parameter names.
``forward(params, state, x, p, cast, cfg) -> logits``
    The plain pixel-domain network on images ``x`` ``(N, C, H, W)``.
    ``p`` is the band projector (applied wherever the program
    truncates bands) and ``cast`` the operand cast (the identity, or
    fp8 for the control), applied to every operand of every matrix
    product.  Traced inside ``reference.logits``'s jitted call, which
    sets precision, blocking and the input pixels.  Imports nothing of
    the program.
``model_flops(cfg)``
    FLOPs of one image: 2 x multiply-adds of the equivalent spatial
    network, every tap counted, zero padding included.
"""
