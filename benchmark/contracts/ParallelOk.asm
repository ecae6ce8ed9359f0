; ParallelOk's runtime, assembled by hand (there is no solc here): one
; instruction a line, `name:` is a JUMPDEST, `PUSH2 @name` pushes its offset,
; everything after `;` is comment. ParallelOk.runtime.hex is this file
; assembled (tests/benchmark_checks/test_refcontract.py assembles it again).
;
; It keeps the source's semantics and the compiler's layout of the work:
; selector dispatch, each string argument copied from calldata to memory,
; the mapping's slot keccak256(bytes(name) ++ uint256(0)), SLOAD, SUB / ADD
; modulo 2^256, SSTORE, `from` stored before `to` is loaded. It leaves out
; what solc adds around that: the free-memory pointer, the callvalue check,
; the ABI decoder's range checks (benchmark/configs/air4-parallelok.json,
; "assumed").

; -- dispatch: calldata shorter than a selector, or an unknown one, reverts
        PUSH1 0x04
        CALLDATASIZE
        LT                      ; calldatasize < 4
        PUSH2 @revert
        JUMPI
        PUSH1 0x00
        CALLDATALOAD
        PUSH1 0xe0
        SHR                     ; the selector
        DUP1
        PUSH4 0x9b80b050        ; transfer(string,string,uint256)
        EQ
        PUSH2 @transfer
        JUMPI
        DUP1
        PUSH4 0x8a42ebe9        ; set(string,uint256)
        EQ
        PUSH2 @set
        JUMPI
        DUP1
        PUSH4 0x35ee5f87        ; balanceOf(string)
        EQ
        PUSH2 @balanceOf
        JUMPI
revert:
        PUSH1 0x00
        DUP1
        REVERT

; -- transfer(string from, string to, uint256 num)
transfer:
        POP                     ; the selector
        PUSH1 0x44
        CALLDATALOAD            ; num
        PUSH2 @from_slot
        PUSH1 0x04              ; head word of `from`
        PUSH2 @slot_of
        JUMP
from_slot:                      ; num, slot(from)
        DUP1
        SLOAD                   ; num, slot, _balance[from]
        DUP3
        SWAP1
        SUB                     ; _balance[from] - num, modulo 2^256
        SWAP1
        SSTORE                  ; stored before `to` is loaded: from == to nets nothing
        PUSH2 @to_slot
        PUSH1 0x24              ; head word of `to`
        PUSH2 @slot_of
        JUMP
to_slot:                        ; num, slot(to)
        DUP1
        SLOAD
        DUP3
        ADD                     ; _balance[to] + num, modulo 2^256
        SWAP1
        SSTORE
        STOP

; -- set(string name, uint256 num)
set:
        POP
        PUSH1 0x24
        CALLDATALOAD            ; num
        PUSH2 @set_slot
        PUSH1 0x04
        PUSH2 @slot_of
        JUMP
set_slot:                       ; num, slot(name)
        SSTORE
        STOP

; -- balanceOf(string name) returns (uint256)
balanceOf:
        POP
        PUSH2 @balance_slot
        PUSH1 0x04
        PUSH2 @slot_of
        JUMP
balance_slot:                   ; slot(name)
        SLOAD
        PUSH1 0x00
        MSTORE
        PUSH1 0x20
        PUSH1 0x00
        RETURN

; -- slot_of: (return address, calldata position of a string's head word)
;    -> keccak256(bytes(string) ++ uint256(0)), the slot of _balance[string]
slot_of:
        CALLDATALOAD            ; ret, the string's offset in the arguments
        PUSH1 0x04
        ADD                     ; ret, position of its length word
        DUP1
        CALLDATALOAD            ; ret, position, length
        SWAP1
        PUSH1 0x20
        ADD                     ; ret, length, position of its bytes
        DUP2
        SWAP1
        PUSH1 0x00
        CALLDATACOPY            ; memory[0 .. length) = the bytes
        PUSH1 0x00
        DUP2
        MSTORE                  ; memory[length .. length + 32) = uint256(0), the mapping's slot
        PUSH1 0x20
        ADD
        PUSH1 0x00
        SHA3                    ; ret, keccak256(memory[0 .. length + 32))
        SWAP1
        JUMP
