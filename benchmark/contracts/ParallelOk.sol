// SPDX-License-Identifier: Apache-2.0
// ParallelOk.sol as java-sdk-demo ships it for `perf.ParallelOkPerf parallelok`
// (FISCO-BCOS 3.x), recalled and not read: see "assumed" in
// benchmark/configs/air4-parallelok.json. The compiler FISCO-BCOS 3.x ships is
// solc 0.6.10: arithmetic is unchecked, modulo 2^256.
pragma solidity >=0.6.10 <0.8.20;

contract ParallelOk {
    mapping(string => uint256) _balance;

    // Just an example, overflow is ok, use 'SafeMath' if needed
    function transfer(string memory from, string memory to, uint256 num) public {
        _balance[from] -= num;
        _balance[to] += num;
    }

    function set(string memory name, uint256 num) public {
        _balance[name] = num;
    }

    function balanceOf(string memory name) public view returns (uint256) {
        return _balance[name];
    }
}
